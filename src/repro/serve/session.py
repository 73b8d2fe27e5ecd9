"""Resident session state for the selection service (ROADMAP item 1).

A :class:`SessionState` is the artifact a long-running server owns: the
ground set, ingested ONCE through the existing wave engine (pipelined
gathers, autotune widths, fault-supervised retries) into per-machine
candidate blocks laid out exactly as round 0 of the tree would see them —
the same virtual-location permutation, the same mesh-padded machine count,
the same zero-padding of empty slots.  Requests then solve against these
resident blocks (:mod:`repro.serve.service`) without ever touching the
source again.

Compared to :func:`repro.core.tree._stream_round0`, ingestion here *stores*
each wave instead of solving it: narrow (bf16/int8) sources are
dequantized on host at store time via the exact fp32 multiply-add of
:meth:`QuantizedSource.dequantize` (bit-identical to the in-kernel device
dequant by the PR 7 contract), so the resident state is uniformly fp32 and
every downstream solve path is dtype-free.

The incremental path (:meth:`SessionState.apply_delta`) edits block
membership in place — deletes clear slots, inserts fill free slots in
machine-major linear order — and bumps a per-machine ``versions`` counter
so the service re-solves only changed blocks.  :meth:`SessionState.rebuild`
re-ingests the base source and replays the delta log through the same
placement rule, which is what makes delta-then-query vs rebuild-then-query
bit-identity a *structural* property (equal resident arrays) rather than a
numerical accident; ``apply_delta`` falls back to it when free capacity
runs out.
"""
from __future__ import annotations

import dataclasses
import json
import os
from typing import Any

import jax
import numpy as np

from repro.core.partition import n_parts
from repro.core.sources import (GroundSetSource, QuantizedSource,
                                dtype_itemsize)
from repro.core.tree import (IngestStats, TreeConfig, _round0_slot_blocks,
                             _round_plan, _wave_planner, _wave_size)
from repro.engine.autotune import AutotunePlanner
from repro.engine.faults import FaultPolicy, FaultSupervisor
from repro.engine.planner import IngestionPlan
from repro.engine.scheduler import EngineConfig, HostWave, run_waves


@dataclasses.dataclass
class DeltaReport:
    """Outcome of one :meth:`SessionState.apply_delta` call."""
    inserted: int
    deleted: int
    changed_machines: list[int]
    rebuilt: bool = False


@dataclasses.dataclass
class SessionState:
    """Resident per-machine ground-set blocks + attrs + membership.

    ``blocks[m, s]`` is the fp32 feature row of the item living in machine
    ``m`` slot ``s`` (zeros when ``valid[m, s]`` is False — the tree's
    padding convention), ``attrs`` its constraint attribute row, and
    ``item_ids`` its stable global id (base items are ``0..n_base-1`` in
    source order; inserted items count up from there; ``-1`` = empty).
    ``versions[m]`` increments whenever machine m's membership changes —
    the service's per-request solution caches compare against it to decide
    which blocks to re-solve after a delta.
    """

    blocks: np.ndarray          # (Mp, mu, d) fp32
    attrs: np.ndarray           # (Mp, mu, a) fp32 (a may be 0)
    valid: np.ndarray           # (Mp, mu) bool
    item_ids: np.ndarray        # (Mp, mu) int64, -1 empty
    versions: np.ndarray        # (Mp,) int64
    mu: int
    d: int
    a: int
    L: int
    Mp: int
    seed: int
    permutation: str
    n_base: int
    next_id: int
    generation: int = 0         # bumped by rebuild (geometry/placement reset)
    dropped_rows: int = 0       # rows forfeited by fault-budget wave drops
    cfg: TreeConfig | None = None
    source: GroundSetSource | None = None    # base source (rebuild needs it)
    delta_log: list[dict] = dataclasses.field(default_factory=list)
    ingest_stats: IngestStats | None = None
    engine_stats: Any = None
    fault_stats: Any = None
    _pos: dict[int, tuple[int, int]] = dataclasses.field(default_factory=dict)

    # -- invariants ------------------------------------------------------
    @property
    def n_items(self) -> int:
        return int(self.valid.sum())

    @property
    def free_slots(self) -> int:
        return self.valid.size - self.n_items

    def fingerprint(self) -> str:
        """Cheap identity of the resident membership (not the row bytes)."""
        import hashlib
        h = hashlib.sha256()
        h.update(self.item_ids.tobytes())
        h.update(np.asarray([self.generation, self.Mp, self.mu]).tobytes())
        return h.hexdigest()[:16]

    # -- incremental membership ------------------------------------------
    def apply_delta(self, insert_rows: np.ndarray | None = None,
                    delete_ids=None,
                    insert_attrs: np.ndarray | None = None,
                    _log: bool = True) -> DeltaReport:
        """Insert/delete items in place; machine-local, no re-ingestion.

        Deletes clear the slot of each given item id; inserts take fresh
        sequential ids and fill free slots lowest-linear-index-first
        (machine-major) — the one canonical placement rule, shared with the
        rebuild replay.  Falls back to :meth:`rebuild` when the inserts
        outnumber the free slots (geometry must grow).  Returns a
        :class:`DeltaReport`; ``changed_machines`` lists every machine
        whose membership changed (its ``versions`` entry was bumped).
        """
        ins = (np.zeros((0, self.d), np.float32) if insert_rows is None
               else np.asarray(insert_rows, np.float32).reshape(-1, self.d))
        dels = [int(i) for i in (delete_ids if delete_ids is not None else [])]
        if self.a:
            assert insert_attrs is not None or not len(ins), (
                "session carries attribute columns — inserts need attrs")
        iattrs = (np.zeros((len(ins), self.a), np.float32)
                  if insert_attrs is None
                  else np.asarray(insert_attrs, np.float32).reshape(
                      len(ins), self.a))
        new_ids = list(range(self.next_id, self.next_id + len(ins)))
        if _log:
            self.delta_log.append({
                "insert_rows": ins.copy(), "insert_attrs": iattrs.copy(),
                "insert_ids": list(new_ids), "delete_ids": list(dels)})

        changed: set[int] = set()
        for did in dels:
            if did not in self._pos:
                raise KeyError(f"delete of unknown/already-deleted id {did}")
            m, s = self._pos.pop(did)
            self.valid[m, s] = False
            self.item_ids[m, s] = -1
            self.blocks[m, s] = 0.0
            if self.a:
                self.attrs[m, s] = 0.0
            changed.add(m)

        if len(ins) > self.free_slots:
            # capacity exhausted: grow the geometry by full rebuild (the
            # log entry above already records this delta, so the replay
            # includes it)
            self.rebuild()
            return DeltaReport(inserted=len(ins), deleted=len(dels),
                               changed_machines=list(range(self.Mp)),
                               rebuilt=True)

        free = np.flatnonzero(~self.valid.reshape(-1))[:len(ins)]
        for j, lin in enumerate(free):
            m, s = divmod(int(lin), self.mu)
            self.valid[m, s] = True
            self.item_ids[m, s] = new_ids[j]
            self.blocks[m, s] = ins[j]
            if self.a:
                self.attrs[m, s] = iattrs[j]
            self._pos[new_ids[j]] = (m, s)
            changed.add(m)
        self.next_id += len(ins)
        for m in sorted(changed):
            self.versions[m] += 1
        return DeltaReport(inserted=len(ins), deleted=len(dels),
                          changed_machines=sorted(changed))

    def rebuild(self) -> None:
        """Re-ingest the base source and replay the delta log.

        The replay applies every logged delta through the same placement
        rule as the incremental path, so (absent a geometry change) the
        resident arrays after ``apply_delta`` and after
        ``rebuild`` are equal element-for-element — the serve layer's
        delta-vs-rebuild bit-identity pin rests on this.  Geometry grows
        (larger L) only when the live-item high-water mark outruns the
        current capacity.
        """
        if self.source is None or self.cfg is None:
            raise RuntimeError("rebuild needs the base source (sessions "
                               "restored from a checkpoint are frozen)")
        live, high = self.n_base, self.n_base
        for e in self.delta_log:
            live += len(e["insert_ids"]) - len(e["delete_ids"])
            high = max(high, live)
        L_new = self.L if high <= self.L * self.mu else n_parts(high, self.mu)
        log = self.delta_log
        fresh = ingest(self.source, self.cfg, attrs=self._base_attrs(),
                       _L=L_new)
        for f in ("blocks", "attrs", "valid", "item_ids", "versions"):
            setattr(self, f, getattr(fresh, f))
        self.L, self.Mp = fresh.L, fresh.Mp
        self.next_id = fresh.next_id
        self._pos = fresh._pos
        self.dropped_rows = fresh.dropped_rows
        self.delta_log = []
        for e in log:
            rep = self.apply_delta(insert_rows=e["insert_rows"],
                                   insert_attrs=e["insert_attrs"],
                                   delete_ids=e["delete_ids"], _log=False)
            assert not rep.rebuilt, "rebuild geometry must fit the replay"
            # replayed inserts must land on their original ids
            assert list(range(self.next_id - len(e["insert_ids"]),
                              self.next_id)) == e["insert_ids"] or \
                e["insert_ids"] == [], e["insert_ids"]
        self.delta_log = log
        self.generation += 1

    def _base_attrs(self) -> np.ndarray | None:
        return getattr(self, "_attrs_np", None)

    # -- persistence ------------------------------------------------------
    def save(self, path: str) -> None:
        """Atomic checkpoint of the resident state (npz + json meta)."""
        os.makedirs(path, exist_ok=True)
        tmp = os.path.join(path, ".session.tmp.npz")   # np.savez wants .npz
        np.savez(tmp, blocks=self.blocks, attrs=self.attrs,
                 valid=self.valid, item_ids=self.item_ids,
                 versions=self.versions)
        os.replace(tmp, os.path.join(path, "session.npz"))
        meta = {"mu": self.mu, "d": self.d, "a": self.a, "L": self.L,
                "Mp": self.Mp, "seed": self.seed,
                "permutation": self.permutation, "n_base": self.n_base,
                "next_id": self.next_id, "generation": self.generation,
                "dropped_rows": self.dropped_rows}
        tmpj = os.path.join(path, ".session.json.tmp")
        with open(tmpj, "w") as f:
            json.dump(meta, f)
        os.replace(tmpj, os.path.join(path, "session.json"))

    @classmethod
    def load(cls, path: str) -> "SessionState":
        with open(os.path.join(path, "session.json")) as f:
            meta = json.load(f)
        z = np.load(os.path.join(path, "session.npz"))
        st = cls(blocks=z["blocks"], attrs=z["attrs"], valid=z["valid"],
                 item_ids=z["item_ids"], versions=z["versions"], **meta)
        st._rebuild_pos()
        return st

    def _rebuild_pos(self) -> None:
        self._pos = {}
        for m, s in zip(*np.nonzero(self.valid)):
            self._pos[int(self.item_ids[m, s])] = (int(m), int(s))


def ingest(source, cfg: TreeConfig, *, attrs: np.ndarray | None = None,
           fault_injector=None, wave_schedule=None,
           _L: int | None = None) -> SessionState:
    """Stream a ground set into a resident session through the wave engine.

    The machinery is round 0 of the tree minus the solve: the same
    ``_round_plan`` / ``_round0_slot_blocks`` placement (dense or Feistel
    permutation, ``cfg.seed``-keyed), the same wave planner (fixed width,
    ``capacity_bytes``-derived, autotuned, or an injected test schedule),
    the same sync/pipelined scheduler, multi-host ingestion plan, and
    PR 6 fault supervision (retries, hedges, host eviction; waves past the
    retry budget drop their rows against the Lemma 3.4 budget and leave
    those machines empty).  Each wave's rows land in the session arrays
    instead of a solver — ingestion is pure data movement, so every engine
    × width × host combination yields identical resident state.

    ``attrs`` overrides the source's attribute channel (``(n, a)`` fp32);
    ``_L`` is the rebuild path's geometry override.
    """
    n, d, mu = source.n, source.d, cfg.capacity
    a = attrs.shape[1] if attrs is not None else source.a
    attrs_np = np.asarray(attrs, np.float32) if attrs is not None else None
    feat_dtype = np.dtype(source.dtype)
    narrow = feat_dtype != np.dtype(np.float32)
    qcols = source.qcols if narrow else 0
    itemsize = dtype_itemsize(feat_dtype) if narrow else 4
    meta_cols = (a + qcols) if narrow else 0
    blk_width = d if narrow else d + a

    L = _L if _L is not None else n_parts(n, mu)
    key = jax.random.PRNGKey(cfg.seed)
    key, kpart, kalg = jax.random.split(key, 3)
    Mp, _keys, _dead = _round_plan(kalg, L, 0, {}, None)
    slot_block = _round0_slot_blocks(kpart, n, L, Mp, mu, cfg.permutation)

    W = _wave_size(cfg, None, 1, Mp, mu, blk_width, itemsize, meta_cols)
    planner, ladder = _wave_planner(cfg, W, 1, Mp, mu, blk_width, None,
                                    wave_schedule, itemsize, meta_cols)
    tracer = cfg.telemetry
    if tracer is not None and isinstance(planner, AutotunePlanner):
        planner.tracer = tracer
    ecfg = EngineConfig(mode=cfg.engine, max_in_flight=cfg.max_in_flight,
                        hosts=cfg.hosts)
    if cfg.prefetch_depth is not None:
        source.prefetch_depth = cfg.prefetch_depth
    plan = IngestionPlan.build(source, cfg.hosts) if cfg.hosts > 1 else None
    plan_state = {"plan": plan}
    cursor = {"w0": 0}

    supervisor: FaultSupervisor | None = None
    if cfg.fault_policy is not None or fault_injector is not None:
        def evict_host(host: int) -> bool:
            p = plan_state["plan"]
            if p is None or p.hosts < 2 or host not in p.host_ids:
                return False
            plan_state["plan"] = p.evict(host)
            return True

        supervisor = FaultSupervisor(
            cfg.fault_policy or FaultPolicy(), total_rows=n,
            injector=fault_injector, rate_hint=planner.gather_rate,
            concurrent_ok=source.supports_concurrent_gather,
            evict_cb=evict_host, tracer=tracer)

    def next_span():
        w0 = cursor["w0"]
        if w0 >= Mp:
            return None
        w = min(planner.next_width(Mp - w0), Mp - w0)
        cursor["w0"] = w0 + w
        return w0, w0 + w

    def gather_rows(idx_flat, fault_hook=None, wave=None):
        p = plan_state["plan"]
        if p is not None:
            rows, src_attrs, per_host = p.gather(
                idx_flat, with_attrs=bool(a) and attrs_np is None,
                parallel=ecfg.mode == "pipelined", fault_hook=fault_hook,
                tracer=tracer, wave=wave)
            row_attrs = (attrs_np[idx_flat] if a and attrs_np is not None
                         else src_attrs)
            return rows, row_attrs, per_host
        if not a:
            return source.gather(idx_flat), None, None
        if attrs_np is not None:
            return source.gather(idx_flat), attrs_np[idx_flat], None
        rows, row_attrs = source.gather_with_attrs(idx_flat)
        return rows, row_attrs, None

    def gather(i: int) -> HostWave | None:
        span = next_span()
        if span is None:
            return None
        w0, w1 = span
        idx_w = slot_block(w0, w1)                          # (Wb, mu)
        idx_flat = np.maximum(idx_w, 0).reshape(-1)
        valid = idx_w >= 0
        if supervisor is None:
            rows, row_attrs, per_host = gather_rows(idx_flat, wave=i)
        else:
            def attempt_fn(attempt: int):
                hook = (fault_injector.host_hook(i, attempt)
                        if fault_injector is not None else None)
                return gather_rows(idx_flat, fault_hook=hook, wave=i)

            gathered, dropped = supervisor.gather(
                i, machines=w1 - w0, rows=int(valid.sum()),
                attempt_fn=attempt_fn)
            if dropped:
                return HostWave(payload=(None, None, None, valid, w0, w1),
                                machines=w1 - w0, rows=(w1 - w0) * mu,
                                bytes_moved=0, per_host_rows=None,
                                last=w1 >= Mp)
            rows, row_attrs, per_host = gathered
        wire_bytes = np.asarray(rows).nbytes + (
            np.asarray(row_attrs).nbytes if row_attrs is not None else 0)
        if narrow:
            qmeta = source.gather_qmeta(idx_flat) if qcols else None
            wire_bytes += qmeta.nbytes if qmeta is not None else 0
            rows = QuantizedSource.dequantize(np.asarray(rows), qmeta)
        feat = np.where(valid[..., None],
                        np.asarray(rows, np.float32).reshape(w1 - w0, mu, d),
                        np.float32(0.0))
        if a:
            am = np.where(valid[..., None],
                          np.asarray(row_attrs, np.float32).reshape(
                              w1 - w0, mu, a), np.float32(0.0))
        else:
            am = np.zeros((w1 - w0, mu, 0), np.float32)
        return HostWave(payload=(feat, am, idx_w, valid, w0, w1),
                        machines=w1 - w0, rows=(w1 - w0) * mu,
                        bytes_moved=wire_bytes, per_host_rows=per_host,
                        last=w1 >= Mp)

    blocks = np.zeros((Mp, mu, d), np.float32)
    attr_blk = np.zeros((Mp, mu, a), np.float32)
    vmask = np.zeros((Mp, mu), bool)
    ids = np.full((Mp, mu), -1, np.int64)
    dropped_rows = [0]

    def store(i: int, payload):
        feat, am, idx_w, valid, w0, w1 = payload
        if feat is None:            # forfeited wave: machines stay empty
            dropped_rows[0] += int(valid.sum())
            return None
        blocks[w0:w1] = feat
        attr_blk[w0:w1] = am
        vmask[w0:w1] = valid
        ids[w0:w1] = np.where(valid, idx_w.astype(np.int64), -1)
        return None

    estats = run_waves(None, gather, store, ecfg, on_trace=planner.observe,
                       tracer=tracer)
    if supervisor is not None:
        estats.fault_stats = supervisor.stats
    assert cursor["w0"] == Mp, (cursor["w0"], Mp)

    peak_rows = max(t.rows for t in estats.traces)
    stats = IngestStats(
        wave_machines=W, waves=estats.waves, peak_wave_rows=peak_rows,
        peak_wave_bytes=peak_rows * (blk_width * itemsize + meta_cols * 4),
        total_machines=Mp, attr_dim=a,
        wave_seconds=[t.gather_s + t.solve_s for t in estats.traces],
        wave_bytes=[t.bytes_moved for t in estats.traces],
        total_bytes=estats.bytes_moved, wall_seconds=estats.wall_s)
    if cfg.capacity_bytes is not None:
        assert stats.peak_wave_bytes <= cfg.capacity_bytes, (
            stats.peak_wave_bytes, cfg.capacity_bytes)

    st = SessionState(
        blocks=blocks, attrs=attr_blk, valid=vmask, item_ids=ids,
        versions=np.zeros((Mp,), np.int64), mu=mu, d=d, a=a, L=L, Mp=Mp,
        seed=cfg.seed, permutation=cfg.permutation, n_base=n, next_id=n,
        dropped_rows=dropped_rows[0], cfg=cfg, source=source,
        ingest_stats=stats,
        engine_stats=estats, fault_stats=getattr(estats, "fault_stats", None))
    st._attrs_np = attrs_np
    st._rebuild_pos()
    return st

"""Multi-host ingestion planner — sharding the round-0 gather across hosts.

The paper's premise makes every wave's (machine, slot) → item assignment a
pure function of the run key (the Feistel scheme gives any host O(1)-state
access to any slot slice; the dense scheme shares one materialized
permutation), so the *gather* itself — the real round-0 bandwidth bill —
can shard across processes with no coordination beyond the key: host p
owns a contiguous item-index range [lo_p, hi_p) of the ground set and
serves exactly the wave slots whose items fall inside it.

This module is the planning + routing layer:

  * :func:`IngestionPlan.build` splits the ground set into per-host
    :class:`HostShard`\\ s (aligned to source shard boundaries when the
    source exposes them, so no lazy shard is split between hosts).
  * :meth:`IngestionPlan.gather` routes a wave's flat item indices to their
    owning hosts, gathers each host's hits from its *local* source view,
    and stitches the wave matrix back together in index order —
    bit-identical to a single-host gather of the same indices.

Single-process emulation (this container, CI) runs every host shard in one
process: each shard's :class:`repro.core.sources.SlicedSource` still
*asserts* that only locally-owned indices reach it, so the locality
contract a real multi-process deployment depends on is enforced, not
assumed.  In a real deployment each process builds the plan from the same
key, keeps only its own shard's loaders, and dispatches its waves; the
emulated planner additionally parallelizes per-host gathers with threads
so the engine's overlap measurements reflect hosts working concurrently.
"""
from __future__ import annotations

import dataclasses
from concurrent.futures import ThreadPoolExecutor
from typing import TYPE_CHECKING, Callable, Tuple

import numpy as np

from repro.engine.telemetry import span

if TYPE_CHECKING:  # typing only — keeps repro.engine importable before
    from repro.core.sources import GroundSetSource  # repro.core finishes


@dataclasses.dataclass
class HostShard:
    """One ingestion host's slice of the ground set."""
    host: int                   # stable host id (survives evictions)
    lo: int                     # first owned global item index
    hi: int                     # one past the last owned global item index
    source: GroundSetSource     # local view; rejects non-local indices


class IngestionPlan:
    """Routing table from global item indices to ingestion hosts."""

    def __init__(self, shards: list[HostShard],
                 parent: GroundSetSource | None = None):
        assert shards and shards[0].lo == 0
        for a, b in zip(shards, shards[1:]):
            assert a.hi == b.lo, "host ranges must tile [0, n)"
        self.shards = shards
        self.parent = parent          # unsliced source; enables evict()
        self.n = shards[-1].hi
        self._los = np.asarray([s.lo for s in shards], np.int64)

    @property
    def hosts(self) -> int:
        return len(self.shards)

    @property
    def host_ids(self) -> list[int]:
        return [s.host for s in self.shards]

    @classmethod
    def build(cls, source: GroundSetSource, hosts: int) -> "IngestionPlan":
        """Split ``source`` into ``hosts`` near-equal contiguous shards.

        Split points come from :meth:`GroundSetSource.host_split_points`,
        which shard-backed sources override to align host boundaries with
        their native shard boundaries (a lazy shard loader then belongs to
        exactly one host).
        """
        assert 1 <= hosts <= source.n, (hosts, source.n)
        bounds = source.host_split_points(hosts)
        assert bounds[0] == 0 and bounds[-1] == source.n
        return cls([HostShard(host=p, lo=lo, hi=hi,
                              source=source.slice(lo, hi))
                    for p, (lo, hi) in enumerate(zip(bounds, bounds[1:]))],
                   parent=source)

    def evict(self, host: int) -> "IngestionPlan":
        """Re-plan around a permanently lost host: its contiguous range is
        re-routed to the surviving neighbors (split at the midpoint when it
        has two; an end host's whole range goes to its single neighbor).

        The survivors get *fresh* ``parent.slice`` views covering their
        widened ranges — re-routing changes only who serves which rows, and
        :meth:`gather` stitches by global index, so a post-eviction gather
        is elementwise identical to the pre-eviction one (the recovery is
        lossless; bit-identity is pinned in tests/test_faults.py).  Host
        ids are stable: survivors keep theirs, which keeps fault traces and
        ``per_host_rows`` attributable across re-plans.
        """
        assert self.parent is not None, "plan built without parent source"
        assert self.hosts >= 2, "cannot evict the only ingestion host"
        pos = [i for i, s in enumerate(self.shards) if s.host == host]
        assert pos, f"host {host} not in plan (already evicted?)"
        i = pos[0]
        dead = self.shards[i]
        survivors = [dataclasses.replace(s) for s in self.shards if s.host != host]
        if i == 0:
            survivors[0].lo = dead.lo                      # right neighbor
        elif i == len(self.shards) - 1:
            survivors[-1].hi = dead.hi                     # left neighbor
        else:
            mid = (dead.lo + dead.hi) // 2
            survivors[i - 1].hi = mid                      # left takes [lo, mid)
            survivors[i].lo = mid                          # right takes [mid, hi)
        shards = [dataclasses.replace(
            s, source=self.parent.slice(s.lo, s.hi)) for s in survivors]
        return IngestionPlan(shards, parent=self.parent)

    def owner_of(self, idx: np.ndarray) -> np.ndarray:
        """Owning host id for each global index."""
        return np.searchsorted(self._los, np.asarray(idx, np.int64),
                               side="right") - 1

    def gather(self, idx: np.ndarray, *, with_attrs: bool = False,
               parallel: bool = False,
               fault_hook: Callable[[HostShard], None] | None = None,
               tracer=None, wave: int | None = None,
               ) -> Tuple[np.ndarray, np.ndarray | None, list[int]]:
        """Rows (+ attrs) for global ``idx``, gathered host-by-host.

        Returns ``(rows, attrs_or_None, per_host_rows)`` with rows in the
        order of ``idx`` — stitching is by boolean index assignment, so the
        result is elementwise identical to a single gather of ``idx``
        against the unsharded source (for ANY plan whose shards tile [0, n),
        which is what makes post-eviction re-plans lossless).
        ``per_host_rows`` is positional — ``per_host_rows[p]`` counts rows
        served by ``self.shards[p]``, whose stable id is ``host_ids[p]``.
        ``parallel=True`` runs the per-host gathers on a thread pool (the
        emulation of hosts reading their shards concurrently); sources
        advertise thread-safe gathers via ``supports_concurrent_gather``.

        ``fault_hook(shard)`` is the chaos-injection seam: called on the
        pulling thread just before each host's local gather (exactly where
        a real deployment's RPC to that host would fail), so injected
        errors/latency land per-host, not per-wave.

        Each host that serves rows opens a ``host.host-gather`` span; a
        ``tracer`` (if given) records it on a named ``host-<id>`` track,
        so a host's gathers line up on one Perfetto lane regardless of
        which pool thread served them, and host skew within a wave is
        visible.  ``wave`` labels the spans.
        """
        idx = np.asarray(idx, np.int64).reshape(-1)
        owner_pos = np.searchsorted(self._los, idx, side="right") - 1
        first = self.shards[0].source
        rows = np.zeros((idx.size, first.d), first.dtype)
        attrs = np.zeros((idx.size, first.a), np.float32) if with_attrs else None
        per_host = [0] * len(self.shards)

        def pull(pos_shard):
            pos, shard = pos_shard
            hit = owner_pos == pos
            if not hit.any():
                return pos, hit, None, None
            if fault_hook is not None:
                fault_hook(shard)
            local_idx = idx[hit]
            with span("host.host-gather", tracer=tracer,
                      track=f"host-{shard.host}", host=shard.host,
                      rows=int(local_idx.size),
                      **({} if wave is None else {"wave": wave})):
                if with_attrs:
                    r, a = shard.source.gather_with_attrs(local_idx)
                else:
                    r, a = shard.source.gather(local_idx), None
            return pos, hit, r, a

        parallel = parallel and len(self.shards) > 1 and all(
            s.source.supports_concurrent_gather for s in self.shards)
        if parallel:
            with ThreadPoolExecutor(max_workers=len(self.shards)) as ex:
                results = list(ex.map(pull, enumerate(self.shards)))
        else:
            results = [pull(ps) for ps in enumerate(self.shards)]

        for pos, hit, r, a in results:
            if r is None:
                continue
            rows[hit] = r
            if with_attrs:
                attrs[hit] = a
            per_host[pos] = int(hit.sum())
        return rows, attrs, per_host

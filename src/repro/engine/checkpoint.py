"""Async double-buffered checkpoint writer — hiding the round-boundary write.

The tree driver checkpoints ``A_t`` (rows, masks, best solution, PRNG-
replayable round counter, oracle totals) at every round boundary so a run
is restartable at any round.  Synchronously, that write serializes the
boundary:

    round_t → [snapshot → serialize → fsync-rename] → round_{t+1}

This module moves the serialize-and-write off the round loop:

    round_t → snapshot ┐
                       ├ (background write of ckpt_t)
    round_{t+1} ───────┘            wall ≈ max(round_{t+1}, ckpt_t)

* **Snapshot** stays on the caller thread: the device→host pulls produce
  fresh host numpy buffers, so the background writer never touches JAX or
  shares mutable state with the next round.
* **Double buffering / write barrier**: at most one write is in flight;
  ``submit`` first waits out the previous round's write (that stall is
  the only checkpoint time the round loop pays, recorded as ``wait_s``),
  then hands the new snapshot to a fresh daemon thread.  ``wait()`` is
  the explicit barrier before the final result — and ``abort()`` the
  quiet one on failure paths — so exact resume semantics are preserved:
  when ``tree_maximize`` returns (or raises), no write is in flight.
* **Crash safety** is inherited from the serializer: writes land in a tmp
  file and are atomically renamed, so a process killed mid-write leaves
  the previous complete checkpoint in place — resume is bit-identical to
  resuming the synchronous writer's file (pinned by
  tests/test_autotune.py's kill-mid-write tests).
* **Failure propagation**: a write error (disk full, serializer bug) is
  re-raised on the caller thread at the next barrier — never swallowed,
  never later than the run's return.

The writer is policy-free about the serialization format: it is handed
the same ``write_fn`` the synchronous path calls (``tree._save_round``),
so the two paths can never drift.
"""
from __future__ import annotations

import os
import re
import shutil
import threading
from typing import Any, Callable

import numpy as np

from repro.engine.stats import CheckpointStats, RoundCheckpoint
from repro.engine.telemetry import span

# ---------------------------------------------------------------------------
# Round-checkpoint file layout: rotation, crash-safe cleanup, resume lookup.
#
# One file per round boundary, ``tree_round_r{t:04d}.npz``, written tmp →
# atomic rename, plus a legacy "latest" pointer ``tree_round.npz`` refreshed
# on every write (hardlink + rename, so it is also atomic and never a
# partial file) — existing resume paths and tests that open the legacy name
# keep working unchanged.  ``keep`` bounds disk growth the same way train's
# ``CheckpointManager`` rotates ``step_*`` dirs: only the newest ``keep``
# rotated rounds survive a write.  A crash mid-write leaves only ``*.tmp*``
# litter (the rename never ran), which ``clean_stale_tmp`` sweeps at the
# next run's start.
# ---------------------------------------------------------------------------

_LEGACY_NAME = "tree_round.npz"
_ROUND_RE = re.compile(r"tree_round_r(\d+)\.npz")


def round_checkpoint_path(d: str, round_idx: int) -> str:
    return os.path.join(d, f"tree_round_r{round_idx:04d}.npz")


def _encode_delta(prev_rows: np.ndarray, cur_rows: np.ndarray
                  ) -> dict[str, np.ndarray]:
    """Row-index delta of ``cur_rows`` against ``prev_rows``.

    Algorithm 1 makes ``A_{t+1}`` a union of *selected* ``A_t`` rows, so
    almost every current row is a verbatim byte-copy of some previous row
    (masked slots are zeros).  Encoding: per current row one int —
    a previous-round row index, ``-1`` for an all-zero row, ``-2`` for the
    rare unmatched row stored verbatim in the ``extra`` arrays.  Exact by
    construction (byte-level matching, lowest previous index on ties), so
    reconstruction is bit-identical to a full snapshot.
    """
    prev = np.ascontiguousarray(prev_rows)
    cur = np.ascontiguousarray(cur_rows)
    lut: dict[bytes, int] = {}
    for i in range(len(prev)):
        lut.setdefault(prev[i].tobytes(), i)
    zero = np.zeros((cur.shape[1],), cur.dtype).tobytes()
    idx = np.full((len(cur),), -2, np.int64)
    extra_pos: list[int] = []
    for i in range(len(cur)):
        b = cur[i].tobytes()
        j = lut.get(b)
        if j is not None:
            idx[i] = j
        elif b == zero:
            idx[i] = -1
        else:
            extra_pos.append(i)
    ep = np.asarray(extra_pos, np.int64)
    return {"delta_idx": idx,
            "delta_extra_pos": ep,
            "delta_extra_rows": cur[ep] if len(ep) else
            np.zeros((0, cur.shape[1]), cur.dtype),
            "delta_nrows": np.int64(cur.shape[0]),
            "delta_width": np.int64(cur.shape[1])}


def load_round_checkpoint(path: str) -> dict[str, np.ndarray]:
    """Load one round checkpoint, reconstructing delta files exactly.

    Full snapshots return their arrays as-is; a delta file recursively
    loads its base round from the same directory (rotation retains every
    ancestor down to the nearest full snapshot) and rebuilds ``rows``
    bit-identically.  Drop-in for the ``np.load`` the resume paths used —
    same keys, host numpy values.
    """
    with np.load(path) as z:
        out = {k: z[k] for k in z.files}
    if "delta_base" not in out:
        return out
    base = int(out.pop("delta_base"))
    prev = load_round_checkpoint(
        round_checkpoint_path(os.path.dirname(path) or ".", base))
    prev_rows = np.asarray(prev["rows"])
    idx = np.asarray(out.pop("delta_idx"), np.int64)
    nrows = int(out.pop("delta_nrows"))
    width = int(out.pop("delta_width"))
    rows = np.zeros((nrows, width), prev_rows.dtype)
    hit = idx >= 0
    if hit.any():
        rows[hit] = prev_rows[idx[hit]]
    ep = np.asarray(out.pop("delta_extra_pos"), np.int64)
    if len(ep):
        rows[ep] = out["delta_extra_rows"]
    out.pop("delta_extra_rows", None)
    out["rows"] = rows
    return out


def _chain_rounds(d: str, rounds: list[int]) -> set[int]:
    """``rounds`` plus every delta ancestor down to a full snapshot."""
    need: set[int] = set()
    stack = list(rounds)
    while stack:
        r = stack.pop()
        if r in need:
            continue
        need.add(r)
        p = round_checkpoint_path(d, r)
        if os.path.exists(p):
            with np.load(p) as z:
                if "delta_base" in z.files:
                    stack.append(int(z["delta_base"]))
    return need


def write_round_checkpoint(d: str, round_idx: int, keep: int = 3,
                           delta_every: int = 0, **arrays: Any) -> str:
    """Atomically write one round's snapshot; rotate to the newest ``keep``.

    The snapshot lands in the rotated per-round file AND the legacy latest
    pointer (both via atomic rename — a crash at any instant leaves every
    ``.npz`` in the directory complete).  ``keep <= 0`` disables rotation
    (every round kept).

    ``delta_every`` > 0 stores ``rows`` as a row-index delta against the
    previous round's file when one exists, with a full snapshot every
    ``delta_every`` rounds (and whenever the base is missing — a delta is
    an optimization, never a dependency).  Rotation keeps each retained
    round's whole ancestor chain so :func:`load_round_checkpoint` always
    reconstructs, bit-identical to an all-full-snapshot directory.
    """
    os.makedirs(d, exist_ok=True)
    path = round_checkpoint_path(d, round_idx)
    payload = dict(arrays)
    if (delta_every > 0 and round_idx % delta_every != 0
            and "rows" in payload):
        prev_path = round_checkpoint_path(d, round_idx - 1)
        if os.path.exists(prev_path):
            prev = load_round_checkpoint(prev_path)
            rows = np.asarray(payload.pop("rows"))
            payload.update(_encode_delta(np.asarray(prev["rows"]), rows),
                           delta_base=np.int64(round_idx - 1))
    tmp = path + ".tmp.npz"               # np.savez appends .npz otherwise
    np.savez(tmp, round=round_idx, **payload)
    os.replace(tmp, path)
    _refresh_latest(d, path)
    if keep > 0:
        existing = list_round_checkpoints(d)
        need = _chain_rounds(d, [r for r, _ in existing[-keep:]])
        for old_round, old_path in existing[:-keep]:
            if old_round != round_idx and old_round not in need:
                os.unlink(old_path)
    return path


def _refresh_latest(d: str, path: str) -> None:
    """Point the legacy ``tree_round.npz`` at ``path`` atomically."""
    tmp = os.path.join(d, _LEGACY_NAME + ".tmp")
    try:
        if os.path.exists(tmp):
            os.unlink(tmp)
        os.link(path, tmp)                # cheap: no data copy
    except OSError:                       # filesystem without hardlinks
        shutil.copyfile(path, tmp)
    os.replace(tmp, os.path.join(d, _LEGACY_NAME))


def list_round_checkpoints(d: str) -> list[tuple[int, str]]:
    """Rotated round checkpoints as ``(round, path)``, oldest first."""
    if not os.path.isdir(d):
        return []
    out = [(int(m.group(1)), os.path.join(d, f))
           for f in os.listdir(d) if (m := _ROUND_RE.fullmatch(f))]
    return sorted(out)


def latest_round_checkpoint(d: str) -> str | None:
    """Newest complete round checkpoint to resume from, or None.

    Prefers the highest rotated round; falls back to the legacy latest
    pointer (directories written before rotation existed hold only that).
    """
    rounds = list_round_checkpoints(d)
    if rounds:
        return rounds[-1][1]
    legacy = os.path.join(d, _LEGACY_NAME)
    return legacy if os.path.exists(legacy) else None


def clean_stale_tmp(d: str) -> list[str]:
    """Remove ``*.tmp`` / ``*.tmp.npz`` litter a crashed writer left behind.

    Safe by construction: every live checkpoint is an atomically renamed
    ``.npz`` whose name never contains ``.tmp``, so anything matching is an
    interrupted write (droppable — its round never counted as saved).
    Called at run start (the writer process owns the directory again).
    Returns the removed paths, newest-crash debris included, for logging.
    """
    removed = []
    if not os.path.isdir(d):
        return removed
    for f in os.listdir(d):
        if ".tmp" in f and f.startswith("tree_round"):
            p = os.path.join(d, f)
            os.unlink(p)
            removed.append(p)
    return removed


class AsyncCheckpointWriter:
    """Background round-checkpoint writer with an explicit write barrier.

    Each background write is a ``ckpt.write`` span (on the writer
    thread's own track — Perfetto shows it running under the next round's
    compute) and each barrier wait a ``ckpt.wait`` span (on the caller's
    track — the only checkpoint time the round loop paid); ``tracer`` (if
    given) records both.
    """

    def __init__(self, write_fn: Callable[..., None], tracer=None):
        self._write_fn = write_fn
        self._thread: threading.Thread | None = None
        self._pending_round: int | None = None
        self._exc: BaseException | None = None
        self._write_s: dict[int, float] = {}
        self._wait_s: dict[int, float] = {}
        self._order: list[int] = []
        self.tracer = tracer

    # -- barrier ----------------------------------------------------------
    def _join_pending(self) -> float:
        """Wait out the in-flight write; returns the caller's stall time."""
        if self._thread is None:
            return 0.0
        with span("ckpt.wait", tracer=self.tracer,
                  round=self._pending_round) as w:
            self._thread.join()
        stall = w.t1 - w.t0
        self._thread = None
        if self._pending_round is not None:
            self._wait_s[self._pending_round] = stall
            self._pending_round = None
        return stall

    def wait(self) -> None:
        """Write barrier: block until no write is in flight, re-raising any
        write failure on the caller thread (final result / pre-snapshot)."""
        self._join_pending()
        if self._exc is not None:
            exc, self._exc = self._exc, None
            raise exc

    def abort(self) -> None:
        """Failure-path barrier: drain the in-flight write but keep the
        original exception as the one the caller sees (a secondary write
        error would mask the root cause)."""
        self._join_pending()
        self._exc = None

    # -- submission -------------------------------------------------------
    def submit(self, round_idx: int, *args: Any, **kwargs: Any) -> None:
        """Hand one round's host-snapshot buffers to the background writer.

        Blocks only while the *previous* round's write is still running
        (the double-buffer barrier) — that stall is recorded against the
        previous round; the new write then runs concurrently with
        whatever the caller does next.
        """
        self.wait()

        def work():
            with span("ckpt.write", tracer=self.tracer,
                      round=round_idx) as w:
                try:
                    self._write_fn(*args, **kwargs)
                except BaseException as exc:  # re-raised at the next barrier
                    self._exc = exc
            self._write_s[round_idx] = w.t1 - w.t0

        self._pending_round = round_idx
        self._order.append(round_idx)
        self._thread = threading.Thread(
            target=work, name=f"ckpt-write-r{round_idx}", daemon=True)
        self._thread.start()

    # -- accounting -------------------------------------------------------
    def stats(self) -> CheckpointStats:
        """Per-round write/stall record (call after the final barrier)."""
        assert self._thread is None, "stats() before the final barrier"
        return CheckpointStats(mode="async", rounds=[
            RoundCheckpoint(round=r, write_s=self._write_s.get(r, 0.0),
                            wait_s=self._wait_s.get(r, 0.0))
            for r in self._order])

"""Trace inspector for the unified telemetry layer.

    PYTHONPATH=src python -m repro.launch.tracetool trace.json \
        [--manifest run_manifest.json] [--limit N] [--tol 1e-6]

Reads the Chrome ``trace_event`` JSON exported by
:class:`repro.engine.telemetry.Tracer` (``--trace-out``) and prints:

  * an event census (spans / instants / tracks),
  * the top span groups by total seconds (``top_spans``),
  * the wave overlap ratio **recomputed from the raw gather/solve span
    intervals** (:func:`wave_overlap_from_spans` — the same arithmetic
    ``EngineStats`` applies to its ``WaveTrace`` timestamps).

With ``--manifest`` it additionally validates the :class:`RunManifest`
(required fields present) and cross-checks the manifest's reported
``engine.overlap_ratio`` against the span-recomputed value to ``--tol``
(default 1e-6): the console report, the manifest, and the trace file are
three views of one event stream, and this tool proves they agree.

Exit status is non-zero on any validation or cross-check failure, so CI
can gate on it directly (grep the ``cross-check: ... PASS`` line).
"""
from __future__ import annotations

import argparse
import json
import sys

from repro.engine.telemetry import (RunManifest, SpanEvent, top_spans,
                                    wave_overlap_from_spans)


def load_trace(path: str) -> tuple[list[SpanEvent], dict[int, str]]:
    """Parse a Chrome trace back into ``SpanEvent`` records.

    The export stores microseconds relative to the trace epoch; they come
    back as seconds here.  Unrounded floats survive the JSON round-trip,
    so overlap reconstruction holds to float precision.
    """
    with open(path) as f:
        doc = json.load(f)
    events: list[SpanEvent] = []
    tracks: dict[int, str] = {}
    for rec in doc["traceEvents"]:
        ph = rec.get("ph")
        if ph == "M" and rec.get("name") == "thread_name":
            tracks[rec["tid"]] = rec["args"]["name"]
        elif ph in ("X", "i"):
            t0 = rec["ts"] / 1e6
            t1 = t0 + (rec.get("dur", 0.0) / 1e6)
            events.append(SpanEvent(
                name=rec["name"], cat=rec.get("cat", ""), t0=t0, t1=t1,
                track=rec["tid"], phase=ph, args=rec.get("args", {})))
    return events, tracks


def span_overlap(events: list[SpanEvent]) -> tuple[float, float, int]:
    """``(span_wall, overlap, n_waves)`` from the wave-category spans."""
    gathers = [(e.t0, e.t1) for e in events
               if e.cat == "wave" and e.name == "gather" and e.phase == "X"]
    solves = [(e.t0, e.t1) for e in events
              if e.cat == "wave" and e.name == "solve" and e.phase == "X"]
    wall, ov = wave_overlap_from_spans(gathers, solves)
    return wall, ov, len(solves)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("trace", help="Chrome trace JSON (--trace-out)")
    ap.add_argument("--manifest", default=None,
                    help="RunManifest JSON to validate and cross-check "
                         "against the trace")
    ap.add_argument("--limit", type=int, default=10,
                    help="top span groups to print")
    ap.add_argument("--tol", type=float, default=1e-6,
                    help="overlap cross-check tolerance")
    args = ap.parse_args(argv)

    events, tracks = load_trace(args.trace)
    n_spans = sum(1 for e in events if e.phase == "X")
    n_inst = len(events) - n_spans
    print(f"trace: {len(events)} events ({n_spans} spans, "
          f"{n_inst} instants) tracks={len(tracks)}")
    for tid in sorted(tracks):
        print(f"  track {tid}: {tracks[tid]}")

    print(f"top spans (by total seconds, limit={args.limit}):")
    for row in top_spans(events, limit=args.limit):
        print(f"  {row['cat']}/{row['name']}: count={row['count']} "
              f"total={row['total_s']:.3f}s mean={row['mean_s']:.4f}s")

    wall, ov, n_waves = span_overlap(events)
    if n_waves:
        print(f"overlap(spans): waves={n_waves} wall={wall:.3f}s "
              f"overlap={ov:.2%}")

    status = 0
    if args.manifest:
        m = RunManifest.load(args.manifest)
        problems = m.validate()
        if problems:
            status = 1
            for p in problems:
                print(f"manifest: INVALID — {p}")
        else:
            print(f"manifest: OK fingerprint={m.config_fingerprint} "
                  f"dtype={m.dtype} value={m.run['value']:.6f} "
                  f"rounds={m.run['rounds']}")
        if m.engine is not None and n_waves:
            want = float(m.engine["overlap_ratio"])
            delta = abs(want - ov)
            ok = delta <= args.tol
            status = status or (0 if ok else 2)
            print(f"cross-check: overlap manifest={want:.6f} "
                  f"spans={ov:.6f} delta={delta:.2e} "
                  f"{'PASS' if ok else 'FAIL'} (tol={args.tol:g})")
    elif not events:
        status = 1
        print("trace: EMPTY — no events")
    return status


if __name__ == "__main__":
    sys.exit(main())

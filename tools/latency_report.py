#!/usr/bin/env python
"""Post-mortem latency report over a save_dump artifact.

The live half of the latency plane is ``GET /latency``
(runtime/obsrv.py); this CLI is the post-mortem half: a run that went
wrong saves its flight-recorder rings with
``rafting_tpu.utils.tracelog.save_dump(path, trace,
meta={"latency": node.latency_snapshot()})``, and this tool renders the
embedded snapshot — per-phase and end-to-end percentile tables, the SLO
burn, recent sampled spans with per-phase breakdowns and per-stripe WAL
engine timings — with no engine, device, or live process required (same zero-dependency contract as
tools/dump_timeline.py).

Usage:
    tools/latency_report.py DUMP.json [--spans N] [--json]

``--spans`` caps how many recent spans print (default 8; 0 hides them).
``--json`` re-emits the raw latency snapshot for scripting.
"""

import argparse
import gzip
import json
import os
import sys

sys.path.insert(0, ".")


def _open_dump(path: str):
    """Gzip-transparent read: .gz decompresses; a bare path falls back
    to its .gz sibling when only the compressed form exists."""
    if path.endswith(".gz"):
        return gzip.open(path, "rt")
    if not os.path.exists(path) and os.path.exists(path + ".gz"):
        return gzip.open(path + ".gz", "rt")
    return open(path)


def _fmt_s(v) -> str:
    """Seconds to a human unit (latencies span ns..s)."""
    v = float(v)
    if v >= 1.0:
        return f"{v:.3f}s"
    if v >= 1e-3:
        return f"{v * 1e3:.3f}ms"
    return f"{v * 1e6:.1f}us"


def _percentile_table(doc: dict, out) -> None:
    rows = []
    for name in ("submit_offer", "offer_stage", "stage_fsync",
                 "fsync_send", "send_commit", "commit_apply", "apply_ack"):
        s = (doc.get("phases") or {}).get(name)
        if s:
            rows.append((name, s))
    for key in ("lat_e2e", "lat_read_e2e"):
        s = doc.get(key)
        if s:
            rows.append((key[4:], s))
    if not rows:
        print("  (no completed spans harvested)", file=out)
        return
    print(f"  {'phase':<14s} {'count':>7s} {'p50':>10s} {'p99':>10s} "
          f"{'p999':>10s} {'max':>10s}", file=out)
    for name, s in rows:
        print(f"  {name:<14s} {s.get('count', 0):>7d} "
              f"{_fmt_s(s.get('p50', 0)):>10s} "
              f"{_fmt_s(s.get('p99', 0)):>10s} "
              f"{_fmt_s(s.get('p999', 0)):>10s} "
              f"{_fmt_s(s.get('max', 0)):>10s}", file=out)


def render(doc: dict, spans: int = 8, out=sys.stdout) -> None:
    if not doc.get("enabled", True):
        print("latency plane disabled for this run (RAFT_LAT_SAMPLE=0)",
              file=out)
    sampling = doc.get("sampling") or {}
    if sampling:
        c = sampling.get("counts") or {}
        print(f"sampling: 1/{sampling.get('rate', '?')} "
              f"seed={sampling.get('seed', '?')} "
              f"sampled={c.get('sampled', 0)} ok={c.get('ok', 0)} "
              f"unknown={c.get('unknown', 0)} "
              f"refused={c.get('refused', 0)} "
              f"overflow={c.get('overflow', 0)}", file=out)
    slo = doc.get("slo") or {}
    if slo:
        print(f"slo: target={_fmt_s(slo.get('target_s', 0))} "
              f"e2e_p999={_fmt_s(slo.get('e2e_p999_s', 0))} "
              f"burn_ratio={slo.get('burn_ratio', 0):.4f}", file=out)
    print("percentiles:", file=out)
    _percentile_table(doc, out)
    recent = doc.get("recent") or []
    if spans and recent:
        print(f"recent spans (last {min(spans, len(recent))} "
              f"of {len(recent)}):", file=out)
        for sp in recent[-spans:]:
            phases = " ".join(f"{k}={_fmt_s(v)}"
                              for k, v in (sp.get("phases") or {}).items())
            print(f"  seq={sp.get('seq')} {sp.get('kind')} "
                  f"g={sp.get('group')} idx={sp.get('idx')} "
                  f"tick={sp.get('tick')} [{sp.get('outcome')}] {phases}",
                  file=out)
    stripes = doc.get("wal_stripes") or []
    if stripes:
        print("wal engine per-stripe (cumulative):", file=out)
        for s in stripes:
            print(f"  stripe {s.get('stripe', '?')}: "
                  f"stage={_fmt_s(s.get('stage_ns', 0) / 1e9)} "
                  f"fsync={_fmt_s(s.get('fsync_ns', 0) / 1e9)} "
                  f"pack={_fmt_s(s.get('pack_ns', 0) / 1e9)} "
                  f"bytes={s.get('bytes', 0)} "
                  f"fsyncs={s.get('fsync_calls', 0)}", file=out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("dump", help="JSON artifact written by "
                                 "tracelog.save_dump (or a raw "
                                 "latency_snapshot() document)")
    ap.add_argument("--spans", type=int, default=8,
                    help="recent spans to print (0 hides them)")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="re-emit the raw latency snapshot as JSON")
    args = ap.parse_args(argv)

    with _open_dump(args.dump) as f:
        doc = json.load(f)
    # Accept a full save_dump artifact (snapshot under _meta.latency), a
    # bare meta dict, or a raw latency_snapshot() document.
    lat = doc.get("_meta", doc).get("latency") \
        if isinstance(doc.get("_meta", doc), dict) else None
    if lat is None and ("sampling" in doc or "enabled" in doc):
        lat = doc
    if lat is None:
        print(f"{args.dump}: no latency snapshot found (save the dump "
              "with meta={'latency': node.latency_snapshot()})",
              file=sys.stderr)
        return 1
    if args.as_json:
        print(json.dumps(lat))
        return 0
    try:
        render(lat, spans=args.spans)
    except BrokenPipeError:
        import os
        os.dup2(os.open(os.devnull, os.O_WRONLY), sys.stdout.fileno())
    return 0


if __name__ == "__main__":
    sys.exit(main())

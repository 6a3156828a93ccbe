#!/usr/bin/env python
"""Decode a flight-recorder dump into per-group event timelines.

Post-mortem half of the observability plane: a run that went wrong saves
its raw device event rings with ``rafting_tpu.utils.tracelog.save_dump``
(a JSON artifact under ``artifacts/`` by convention), and this CLI turns
them back into the human timeline — which replica did what, when — with
no engine, device, or live process required.

Usage:
    tools/dump_timeline.py DUMP.json [--group G] [--node N] [--json]

With ``--group`` omitted, every group with events is printed.  ``--node``
selects the node axis of a stacked [N, G, D] cluster dump (default 0).
``--json`` emits machine-readable output instead of the table.

Dumps saved with ``meta={"latency": node.latency_snapshot()}`` also
carry the PR 13 latency plane: sampled lifecycle spans interleave with
the group's flight-recorder events on the shared tick axis (a span
prints after the last event at or before its accept tick).
Use tools/latency_report.py for the percentile/SLO view of the same
snapshot.
"""

import argparse
import importlib.util
import json
import os
import sys

sys.path.insert(0, ".")


def _load_tracelog():
    """Load the decoder module by FILE PATH, not via the package: the
    package __init__ imports the whole engine (jax/flax), and the whole
    point of this CLI is decoding on a box that has neither."""
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "rafting_tpu", "utils", "tracelog.py")
    spec = importlib.util.spec_from_file_location("_tracelog_standalone",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _print_span(sp: dict) -> None:
    phases = " ".join(f"{k}={v * 1e3:.3f}ms"
                      for k, v in (sp.get("phases") or {}).items())
    print(f"  span  tick {sp.get('tick', -1):<8d} seq {sp.get('seq')} "
          f"{sp.get('kind')} idx={sp.get('idx')} "
          f"[{sp.get('outcome')}] {phases}")


def main(argv=None) -> int:
    tracelog = _load_tracelog()
    decode_group, load_dump = tracelog.decode_group, tracelog.load_dump

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("dump", help="JSON dump written by tracelog.save_dump")
    ap.add_argument("--group", type=int, default=None,
                    help="decode one group (default: all with events)")
    ap.add_argument("--node", type=int, default=0,
                    help="node index for stacked cluster dumps")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="emit JSON instead of a table")
    args = ap.parse_args(argv)

    lanes = load_dump(args.dump)
    # Latency-plane meta (optional): sampled spans ride the artifact's
    # _meta lane, which load_dump's typed-lane view drops — read the
    # raw JSON for it
    # (gzip-transparent: dumps may be .json or .json.gz).
    with tracelog._open_dump(args.dump) as f:
        meta = json.load(f).get("_meta") or {}
    lat = meta.get("latency") or {}
    spans_by_g = {}
    for sp in lat.get("recent") or []:
        spans_by_g.setdefault(sp.get("group", -1), []).append(sp)
    stacked = lanes["n"].ndim == 2
    counts = lanes["n"][args.node] if stacked else lanes["n"]
    groups = ([args.group] if args.group is not None
              else [g for g in range(counts.shape[0]) if counts[g] > 0])

    out = []
    for g in groups:
        events, dropped = decode_group(
            lanes, g, node=args.node if stacked else None)
        out.append({"group": g, "events": events, "dropped": dropped,
                    "total": int(counts[g]), "spans": spans_by_g.get(g, [])})
    try:
        if args.as_json:
            print(json.dumps({"groups": out}))
            return 0
        for doc in out:
            head = (f"group {doc['group']}: {doc['total']} events"
                    + (f" ({doc['dropped']} overwritten before this window)"
                       if doc["dropped"] else ""))
            print(head)
            # Interleave sampled spans on the shared tick axis: a span
            # prints after the last event at or before its accept tick.
            spans = sorted(doc["spans"], key=lambda s: s.get("tick", -1))
            si = 0
            for ev in doc["events"]:
                while si < len(spans) \
                        and spans[si].get("tick", -1) <= ev["tick"]:
                    _print_span(spans[si])
                    si += 1
                print(f"  #{ev['seq']:<5d} tick {ev['tick']:<8d} "
                      f"term {ev['term']:<6d} {ev['event']:<22s} "
                      f"aux={tracelog.format_aux(ev['kind'], ev['aux'])}")
            for sp in spans[si:]:
                _print_span(sp)
        if not out:
            print("no events recorded")
    except BrokenPipeError:   # `... | head` is the normal workflow
        import os
        os.dup2(os.open(os.devnull, os.O_WRONLY), sys.stdout.fileno())
    return 0


if __name__ == "__main__":
    sys.exit(main())

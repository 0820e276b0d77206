#!/usr/bin/env python
"""bench_compare — the continuous perf gate (ROADMAP item 5;
``make bench-gate``).

Diffs a bench JSON line (schema 7+ cumulative-emission format) against
the committed ``BENCH_BASELINE.json`` with per-key noise bands, and
exits nonzero on an out-of-band regression — so a perf PR that silently
regresses an earlier tentpole (serve p50 after a codec change, wire RTT
after a socket-option slip, MFU after a remat tweak) fails loudly.

The line to gate is always named: ``--line PATH`` is a file whose LAST
parseable JSON object carries the bench ``extras`` (a raw ``bench.py``
stdout capture works, so does a driver wrapper in the ``parsed``/``tail``
form); ``-`` reads stdin.  The repo commits no bench record to fall back
on — with no ``--line`` the gate says so and exits 2
(``python bench.py wire_micro > line.json; make bench-gate
LINE=line.json``).

Baseline format (``BENCH_BASELINE.json``)::

    {"keys": {
        "<metric>": {"value": <expected>,
                      "direction": "higher" | "lower",
                      "band_rel": <fraction> | "band_abs": <units>,
                      "note": "..."},
        ...}}

``direction: higher`` means bigger is better — the gate fails when the
measured value drops below ``value - band``; ``lower`` fails when it
rises above ``value + band``.  Keys missing from the measured line are
reported and SKIPPED (bench sections are individually best-effort;
``--strict`` turns missing keys into failures).  PERF.md documents the
±1.5 MFU run-to-run noise the MFU band encodes.

Exit codes: 0 in-band, 1 regression (or --strict miss), 2 no usable
line/baseline.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _extras_from_obj(obj):
    """Bench extras from either a bench.py line or a driver wrapper."""
    if not isinstance(obj, dict):
        return None
    if isinstance(obj.get("extras"), dict):
        return obj["extras"]
    if isinstance(obj.get("parsed"), dict):
        return _extras_from_obj(obj["parsed"])
    if isinstance(obj.get("tail"), str):
        return _extras_from_text(obj["tail"])
    return None


def _extras_from_text(text):
    """LAST parseable JSON object with extras wins (the schema-7
    cumulative-emission contract: the freshest line is the truth)."""
    found = None
    for line in text.splitlines():
        line = line.strip()
        if not (line.startswith("{") and line.endswith("}")):
            continue
        try:
            extras = _extras_from_obj(json.loads(line))
        except json.JSONDecodeError:
            continue
        if extras:
            found = extras
    return found


def load_line(path):
    with (sys.stdin if path == "-" else open(path)) as fh:
        text = fh.read()
    try:
        return _extras_from_obj(json.loads(text))
    except json.JSONDecodeError:
        return _extras_from_text(text)


def check(extras, baseline, strict=False):
    """Returns (failures, skipped, checked) finding lists."""
    failures, skipped, checked = [], [], []
    for key, spec in baseline.get("keys", {}).items():
        if key not in extras:
            skipped.append(key)
            continue
        got = float(extras[key])
        want = float(spec["value"])
        if "band_abs" in spec:
            band = float(spec["band_abs"])
        else:
            band = abs(want) * float(spec.get("band_rel", 0.3))
        direction = spec.get("direction", "higher")
        if direction == "higher":
            ok = got >= want - band
            bound = f">= {want - band:.4g}"
        else:
            ok = got <= want + band
            bound = f"<= {want + band:.4g}"
        (checked if ok else failures).append(
            f"{key}: got {got:.4g}, expected {bound} "
            f"(baseline {want:.4g}, {spec.get('note', '')})".rstrip(" ,("))
    if strict:
        failures += [f"{k}: missing from the measured line (--strict)"
                     for k in skipped]
        skipped = []
    return failures, skipped, checked


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--line", default=None,
                    help="bench output file to gate ('-' = stdin)")
    ap.add_argument("--baseline",
                    default=os.path.join(REPO, "BENCH_BASELINE.json"))
    ap.add_argument("--strict", action="store_true",
                    help="missing baseline keys fail instead of skip")
    args = ap.parse_args(argv)

    try:
        with open(args.baseline) as fh:
            baseline = json.load(fh)
    except (OSError, json.JSONDecodeError) as exc:
        print(f"bench-gate: cannot read baseline {args.baseline}: {exc}",
              file=sys.stderr)
        return 2

    if not args.line:
        print("bench-gate: no --line given, nothing gated.  Capture one "
              "(`python bench.py wire_micro > line.json`) and pass it: "
              "`make bench-gate LINE=line.json`", file=sys.stderr)
        return 2
    src, extras = args.line, load_line(args.line)
    if not extras:
        print(f"bench-gate: no parseable bench line in {src}",
              file=sys.stderr)
        return 2

    failures, skipped, checked = check(extras, baseline,
                                       strict=args.strict)
    print(f"bench-gate: {src}: {len(checked)} key(s) in band, "
          f"{len(skipped)} skipped (not measured), "
          f"{len(failures)} regression(s)")
    for k in skipped:
        print(f"  skip  {k}")
    for line in checked:
        print(f"  ok    {line}")
    for line in failures:
        print(f"  FAIL  {line}")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python
"""Record the second fixture, ``toy_1chip_scoped``: ``record_fixture.py``'s
procedure run on a program that carries scopes, kernel names and ``mv.*``
spans (PR 23), for ``benchmarks/trace/program.py``.  On the chip, from the
repo root::

    python benchmarks/trace/record_scoped_fixture.py chiprun_out/fixture

then copy ``toy_1chip_scoped.xplane.pb.gz``, ``.hlo.txt.gz`` and
``.golden.json`` into ``benchmarks/trace/fixtures/``.  ``toy_1chip.*``, made
before the program had names, stays as it is.  The golden file is what
``program.summarize`` gives on the trace, to be checked by hand against
``describe_scoped.txt`` once and held by the tests; it can be written again
anywhere: ``record_scoped_fixture.py --golden <stem>``.  The toy size is for
the fixture only: no number read off it is a measurement.
"""

from __future__ import annotations

import gzip
import json
import os
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

STEM = "toy_1chip_scoped"


def summarize_fixture(stem: str):
    """``program.summarize`` of ``<stem>.xplane.pb.gz``, its ``op_name``s
    read off the trace as a run's readers do."""
    from benchmarks.trace import program, reduce

    with tempfile.TemporaryDirectory() as tmp:
        plain = os.path.join(tmp, "trace.xplane.pb")
        with gzip.open(stem + ".xplane.pb.gz", "rb") as f, \
                open(plain, "wb") as g:
            g.write(f.read())
        return program.summarize(reduce.load_xplane(plain),
                                 program.ScopeIndex.from_xplane(plain))


def golden(stem: str) -> dict:
    prog = summarize_fixture(stem)
    return {"step_programs": prog.step_programs, "busy_s": prog.busy_s,
            "by_phase_s": prog.by_phase_s, "by_scope_s": prog.by_scope_s,
            "by_kernel_s": prog.by_kernel_s,
            "unscoped_top_s": prog.unscoped_s[:10],
            "spans": {k: list(v) for k, v in sorted(prog.spans.items())}}


def write_golden(stem: str) -> None:
    data = golden(stem)
    with open(stem + ".golden.json", "w") as f:
        json.dump(data, f, indent=1)


def main() -> int:
    if len(sys.argv) == 3 and sys.argv[1] == "--golden":
        write_golden(sys.argv[2])
        return 0
    if len(sys.argv) != 2:
        print(__doc__, file=sys.stderr)
        return 2
    from benchmarks.trace import record_fixture

    out_dir = sys.argv[1]
    os.makedirs(out_dir, exist_ok=True)
    plain = record_fixture.record(out_dir, 1)
    stem = os.path.join(out_dir, STEM)
    for suffix in (".xplane.pb.gz", ".hlo.txt.gz"):
        os.replace(os.path.join(out_dir, "toy_1chip" + suffix), stem + suffix)
    with open(os.path.join(out_dir, "describe_scoped.txt"), "w") as out:
        record_fixture.describe(plain, out)
    os.remove(plain)
    write_golden(stem)
    print(json.dumps(golden(stem), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())

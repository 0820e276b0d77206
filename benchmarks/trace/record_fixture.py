#!/usr/bin/env python
"""Record the small device trace that ``benchmarks/tests`` checks the
reduction on, and print what a trace on this machine looks like.

Run on the chip, from the repo root (a four-chip host also records the
data-parallel fixture, whose step holds an all-reduce)::

    python benchmarks/trace/record_fixture.py chiprun_out/fixture

and copy ``toy_<n>chip.xplane.pb.gz`` and ``toy_<n>chip.hlo.txt.gz`` into
``benchmarks/trace/fixtures/``; ``describe.txt`` beside them says what the
planes, lines and events of the trace look like.  It drives the program's
own pieces at a toy size (a two-layer ``TransformerTrainer`` through the
flash kernels, then ``SkipGram``'s fused step) under the benchmark's host
spans, so the op names and plane layout are those of the real cells.  The
toy size is for the fixture only: no number read off it is a measurement.
"""

from __future__ import annotations

import glob
import gzip
import os
import shutil
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)


def record(out_dir: str, chips: int) -> str:
    import jax
    import numpy as np
    from jax.sharding import Mesh

    import multiverso_tpu as mv
    from multiverso_tpu.apps import SkipGram
    from multiverso_tpu.models import TransformerConfig, TransformerTrainer

    span = jax.profiler.TraceAnnotation
    mesh = Mesh(np.asarray(jax.devices()[:chips]), ("dp",))
    mv.init(args=["-updater_type=sgd", "-sync=false", "-log_level=error"],
            mesh=Mesh(np.asarray(jax.devices()[:chips]), ("worker",)))
    cfg = TransformerConfig(vocab_size=1024, dim=256, n_layers=2, n_heads=2,
                            hidden=512, max_seq=512, scan_layers=True,
                            remat=True, remat_policy="dots")
    tr = TransformerTrainer(cfg, mesh, updater_type="sgd")
    rng = np.random.RandomState(0)
    toks = rng.randint(cfg.vocab_size, size=(2 * chips, 512)).astype(np.int32)
    hlo = [tr.lowered_step(toks).compile().as_text()]
    float(tr.train_step_async(toks))
    sg = SkipGram(4096, 300, learning_rate=0.025 * 512, name="fixture_w2v")
    corpus = ((rng.zipf(1.1, size=1200) - 1) % 4096).astype(np.int32)
    sg.train_epoch_fused(corpus, 512, seed=0)
    step, place = sg.make_fused_step()
    ids = place(np.zeros(512, np.int32))
    hlo.append(step.lower(
        *sg.table_in.raw_value(), *sg.table_out.raw_value(), ids, ids,
        place(np.zeros((512, 5), np.int32))).compile().as_text())

    trace_dir = os.path.join(out_dir, f"trace{chips}")
    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    with span("bench.window"):
        prev = None
        for i in range(3):
            with span("bench.make_batch"):
                toks = rng.randint(cfg.vocab_size,
                                   size=toks.shape).astype(np.int32)
            with span("bench.enqueue"):
                loss = tr.train_step_async(toks)
            if prev is not None:
                with span("bench.fetch"):
                    float(prev)
            prev = loss
        with span("bench.fetch"):
            float(prev)
        with span("bench.sleep"):
            time.sleep(0.005)
        with span("bench.epoch_chunk"):
            sg.train_epoch_fused(corpus, 512, seed=1)
    jax.profiler.stop_trace()
    mv.shutdown()
    src = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                 "*.xplane.pb"))[-1]
    dst = os.path.join(out_dir, f"toy_{chips}chip.xplane.pb")
    shutil.copyfile(src, dst)
    with open(dst, "rb") as f, gzip.open(dst + ".gz", "wb", 9) as g:
        g.write(f.read())
    with gzip.open(os.path.join(out_dir, f"toy_{chips}chip.hlo.txt.gz"),
                   "wt") as g:
        g.write("\n".join(hlo))
    return dst


def describe(path: str, out) -> None:
    """Planes, lines, the busiest event names and a few whole events."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    print(f"== {path} ({os.path.getsize(path)} bytes)", file=out)
    for plane in data.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}: {len(lines)} lines", file=out)
        for line in lines:
            events = list(line.events)
            if not events:
                continue
            t0 = min(e.start_ns for e in events)
            t1 = max(e.start_ns + e.duration_ns for e in events)
            print(f"  LINE {line.name!r}: {len(events)} events, "
                  f"{t0:.0f}..{t1:.0f} ns", file=out)
            total = {}
            for e in events:
                s = total.setdefault(e.name, [0, 0.0, e])
                s[0] += 1
                s[1] += e.duration_ns
            top = sorted(total.items(), key=lambda kv: -kv[1][1])[:40]
            for name, (n, dur, e) in top:
                stats = {k: (v if len(str(v)) < 80 else str(v)[:80] + "...")
                         for k, v in e.stats}
                print(f"    {n:5d}x {dur / 1e3:12.1f} us  {name!r}  {stats}",
                      file=out)


def write_golden(stem: str) -> None:
    """Write ``<stem>.golden.json`` from ``<stem>.xplane.pb.gz`` and
    ``<stem>.hlo.txt.gz``: what the reduction gives on the fixture, to be
    checked by hand against ``describe.txt`` once and held by the tests.
    Runs anywhere: ``record_fixture.py --golden <stem>``."""
    import json
    import tempfile

    from benchmarks.trace import reduce

    with tempfile.TemporaryDirectory() as tmp:
        plain = os.path.join(tmp, "trace.xplane.pb")
        with gzip.open(stem + ".xplane.pb.gz", "rb") as f, \
                open(plain, "wb") as g:
            g.write(f.read())
        trace = reduce.load_xplane(plain)
    with gzip.open(stem + ".hlo.txt.gz", "rt") as f:
        summary = reduce.summarize(trace, reduce.HloIndex([f.read()]))
    with open(stem + ".golden.json", "w") as f:
        json.dump({"chips": summary.chips,
                   "step_programs": summary.step_programs,
                   "window_s": summary.window_s, "busy_s": summary.busy_s,
                   "collective_s": summary.collective_s,
                   "collective_exposed_s": summary.collective_exposed_s,
                   "by_category_s": summary.by_category_s,
                   "top_ops_s": summary.by_op_s[:10]}, f, indent=1)


def main() -> int:
    if len(sys.argv) == 3 and sys.argv[1] == "--golden":
        write_golden(sys.argv[2])
        return 0
    import jax

    out_dir = sys.argv[1] if len(sys.argv) > 1 else "chiprun_out/fixture"
    os.makedirs(out_dir, exist_ok=True)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"record_fixture: need a TPU, found '{dev.platform}'")
    with open(os.path.join(out_dir, "describe.txt"), "w") as out:
        print(f"device {dev.device_kind} x {jax.device_count()}", file=out)
        chips = 4 if jax.device_count() >= 4 else 1
        path = record(out_dir, chips)
        describe(path, out)
        shutil.rmtree(os.path.join(out_dir, f"trace{chips}"))
        os.remove(path)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Record the small profiler trace that ``test_xplane.py`` reduces.

    python3 bench/tests/make_trace.py <out.xplane.pb>

Run on a TPU: a two-layer model at small width is served through the
paged int4 bit-plane cache for a few steps, under the harness's
``bench.window`` / ``bench.step`` annotations, and the profiler's
``.xplane.pb`` is copied to ``<out>``.  Prints the planes, lines and the
busiest operations of the trace.
"""

import pathlib
import shutil
import sys
import tempfile

ROOT = pathlib.Path(__file__).resolve().parents[2]


def main(out: str) -> int:
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    import numpy as np

    from bench import harness, xplane

    config = dict(harness.load_cell("qwen3-1.7b.chat-shared").config)
    config.update(hidden_size=256, intermediate_size=512,
                  num_hidden_layers=2, vocab_size=1024)
    config["stack"] = {**config["stack"], "slots": 4, "max_len": 256}
    eng = harness.build_engine(config, seed=0)
    rng = np.random.default_rng(0)
    for n in (64, 96, 128, 160):
        eng.submit(rng.integers(0, 1024, n, dtype=np.int32), 8)
    for _ in range(3):              # compile outside the trace
        eng.step()
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d, profiler_options=harness.profile_options())
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(6):
                with jax.profiler.TraceAnnotation("bench.step"):
                    eng.step()
        jax.profiler.stop_trace()
        path = xplane.find_xplane(d)
        shutil.copy(path, out)
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(out).planes:
        lines = [(ln.name, len(list(ln.events))) for ln in plane.lines]
        print(plane.name, lines)
    s = xplane.reduce(out)
    print("window_s", s.window_s, "busy_s", s.busy_s, "devices", s.devices)
    for name, sec in s.top_ops(15):
        print(f"  {sec * 1e6:10.1f} us  {name}")
    for name, sec in s.top_gaps(10):
        print(f"  gap {sec * 1e6:10.1f} us  {name}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))

"""Record the small v5e trace that bench/tests/test_program_spans.py reads:
a few ticks of the continuous-batching engine (a 2-block cut of the 150M
path, 2 paths x 4 slots, a 256-token cache, flash_decode) under a
bench.window annotation, with the engine's serve.* spans.

    python bench/tools/record_engine_trace.py v5e_engine.xplane.pb
"""
import glob
import shutil
import sys

sys.path.insert(0, "src")
import jax
import numpy as np

from repro.configs import get_config
from repro.models import api
from repro.serving import ContinuousBatchingEngine, EngineOptions, Request

cfg = get_config("dipaco-150m").replace(attn_impl="pallas", num_layers=2)
init = jax.jit(lambda k: api.init_model(k, cfg)[0])
paths = [init(jax.random.key(p)) for p in range(2)]
engine = ContinuousBatchingEngine(cfg, paths, options=EngineOptions(
    cache_len=256, slots_per_path=4, prefill_buckets=(64, 128, 256),
    route_fn=lambda prompt: len(prompt) % 2))
engine.warmup()


def serve(rid0):
    """Three requests, then two more admitted in the tick that decodes
    the first three, then the drain."""
    rng = np.random.default_rng(0)
    for i, (n, new) in enumerate([(40, 3), (90, 5), (130, 4),
                                  (70, 4), (200, 3)]):
        if i == 3:
            engine.step()
        engine.submit(Request(rid=rid0 + i, prompt=rng.integers(
            0, cfg.vocab_size, n, dtype=np.int32), max_new=new))
    while not engine.idle:
        engine.step()


serve(0)                 # compiles the slot writes of these admissions
opts = jax.profiler.ProfileOptions()
opts.python_tracer_level = 0
opts.host_tracer_level = 2
opts.enable_hlo_proto = False
out = sys.argv[1] + ".dir"
shutil.rmtree(out, ignore_errors=True)
jax.profiler.start_trace(out, profiler_options=opts)
with jax.profiler.TraceAnnotation("bench.window"):
    serve(100)
jax.profiler.stop_trace()
shutil.copy(glob.glob(out + "/plugins/profile/*/*.xplane.pb")[0], sys.argv[1])
print("ticks", engine.ticks)

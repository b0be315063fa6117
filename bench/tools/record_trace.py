"""Record the small v5e trace that bench/tests/test_trace_reduce.py reads:
two prefills and three decode steps (with flash_decode) of a 2-block cut
of the 150M path, under bench.* annotations.

    python bench/tools/record_trace.py chiprun_out/v5e_decode.xplane.pb
"""
import glob
import shutil
import sys
import time

sys.path.insert(0, "src")
import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.models import api

cfg = get_config("dipaco-150m").replace(attn_impl="pallas", num_layers=2)
params = jax.jit(lambda k: api.init_model(k, cfg)[0])(jax.random.key(0))
B, T = 4, 256
pre = jax.jit(lambda p, t: api.prefill(p, cfg, {"tokens": t}, T))
dec = jax.jit(lambda p, t, c, i: api.decode_step(p, cfg, {"tokens": t}, c, i))
toks = jnp.zeros((B, 64), jnp.int32)
lg, cache = pre(params, toks)
jax.block_until_ready(dec(params, toks[:, :1], cache,
                          jnp.full((B,), 64, jnp.int32)))
opts = jax.profiler.ProfileOptions()
opts.python_tracer_level = 0
opts.host_tracer_level = 2
opts.enable_hlo_proto = False
out = sys.argv[1] + ".dir"
shutil.rmtree(out, ignore_errors=True)
jax.profiler.start_trace(out, profiler_options=opts)
with jax.profiler.TraceAnnotation("bench.window"):
    for i in range(2):
        with jax.profiler.TraceAnnotation("bench.prefill"):
            jax.block_until_ready(pre(params, toks))
    for i in range(3):
        with jax.profiler.TraceAnnotation("bench.decode"):
            jax.block_until_ready(dec(params, toks[:, :1], cache,
                                      jnp.full((B,), 64 + i, jnp.int32)))
        with jax.profiler.TraceAnnotation("bench.host"):
            time.sleep(0.002)
jax.profiler.stop_trace()
shutil.copy(glob.glob(out + "/plugins/profile/*/*.xplane.pb")[0], sys.argv[1])

"""Compile a serving cell's largest prefill bucket and its stacked decode
tick for a described v5e (no chip needed) and print their memory."""
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, "src")
sys.path.insert(0, "bench")
import jax
import jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding

import harness
import serving
from repro.models import api

cell = sys.argv[1]
bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
entry = next(w for w in bench["workloads"] if w["name"] == cell)
cfgfile = harness.load_json(harness.BENCH / "configs" / f"{entry['config']}.json")
mix = harness.load_json(harness.BENCH / "traffic" / f"{entry['traffic']}.json")
over = json.loads(sys.argv[2]) if len(sys.argv) > 2 else {}
cfg, model, knobs = serving.program_config(cfgfile, over)
P, S, T = knobs["paths"], knobs["slots_per_path"], knobs["cache_len"]
topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
dev = SingleDeviceSharding(topo.devices[0])
params = jax.eval_shape(lambda k: api.init_model(k, cfg)[0], jax.random.key(0))
sds = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=dev)
params = jax.tree.map(sds, params)
stacked = jax.tree.map(lambda x: jax.ShapeDtypeStruct((P,) + x.shape, x.dtype,
                                                      sharding=dev), params)
cache = jax.eval_shape(lambda: api.init_serve_cache(cfg, S, T))
scache = jax.tree.map(lambda x: jax.ShapeDtypeStruct((P,) + x.shape, x.dtype,
                                                     sharding=dev), cache)


def prefill(p, tokens, last):
    logits, c = api.prefill(p, cfg, {"tokens": tokens}, T)
    return jnp.take_along_axis(logits, last[:, None, None], axis=1)[:, 0], c


def decode_one(p, tok, c, idx, mask):
    logits, new = api.serve_step(p, cfg, {"tokens": tok}, c, idx)
    m = lambda n, o: jnp.where(mask.reshape((1, -1) + (1,) * (n.ndim - 2)),
                               n.astype(o.dtype), o)
    return logits[:, 0], jax.tree.map(m, new, c)


out = {}
for length in (max(serving.buckets_for(mix, T)), T):
    c = jax.jit(prefill).lower(
        params, jax.ShapeDtypeStruct((S, length), jnp.int32, sharding=dev),
        jax.ShapeDtypeStruct((S,), jnp.int32, sharding=dev)).compile()
    m = c.memory_analysis()
    out[f"prefill_{S}x{length}"] = {"temp_GB": m.temp_size_in_bytes / 1e9,
                                   "args_GB": m.argument_size_in_bytes / 1e9}
c = jax.jit(jax.vmap(decode_one), donate_argnums=2).lower(
    stacked, jax.ShapeDtypeStruct((P, S, 1), jnp.int32, sharding=dev), scache,
    jax.ShapeDtypeStruct((P, S), jnp.int32, sharding=dev),
    jax.ShapeDtypeStruct((P, S), bool, sharding=dev)).compile()
m = c.memory_analysis()
out["decode_stacked"] = {"temp_GB": m.temp_size_in_bytes / 1e9,
                         "args_GB": m.argument_size_in_bytes / 1e9,
                         "custom_calls": c.as_text().count("tpu_custom_call")}
print(json.dumps(out))
if len(sys.argv) > 3:
    with open(sys.argv[3], "w") as f:
        f.write(c.as_text())

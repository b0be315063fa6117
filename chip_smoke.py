#!/usr/bin/env python3
"""Run DiPaCo's main path once on a TPU, at the paper's path width.

    python chip_smoke.py              # one chip: serve, Pallas decode, train
    python chip_smoke.py --chips 4    # four chips: mesh outer sync only
    JAX_PLATFORMS=cpu python chip_smoke.py --smoke   # CPU rehearsal

One chip, three phases, all at ``dipaco-150m`` ``config()`` widths
(Table 4: 12 blocks, d=896, 16x64 heads, d_ff 3584, vocab 32000, bf16),
with random weights drawn from ``--seed`` (serving; the train launcher
seeds its own init):

* serve — ``repro.launch.serve --engine continuous`` over 4 paths, 8
  slots each, a 1024-token cache; 16 Poisson requests of 256-token
  prompts and 32 new tokens.  Every generated token is checked against
  a float32 full forward of its path (``api.forward_logits`` on prompt
  + generated prefix, f32 matmuls): the token must be within
  ``GAP_MAX`` logits of the reference's best, and at least
  ``AGREE_MIN`` of the tokens must be the reference's argmax.  A
  control decodes 8 prompts greedily, sound and with a planted fault
  (positions one early): the sound run must pass and the faulted one
  fail, so the limits are shown to see a wrong decode.
* pallas decode — the same serve with ``--attn-impl pallas``, so
  ``flash_decode`` runs compiled on every tick (same token check), and
  one decode step of each branch, bf16 and int8 KV, against the float32
  reference at the same position: logits within ``DECODE_TOL``, while
  two planted faults (positions one early; int8 value scales dropped)
  must land outside it.
* train (runs first: it needs most of the chip) — ``repro.launch.train``
  with the ``vector`` backend, levels 2x2 (four stacked workers on the
  chip), batch 2 x 512 tokens per worker, depth cut to ``TRAIN_LAYERS``
  of the 12 blocks: four workers' f32 master weights, AdamW moments,
  global copies and outer momenta need 12.0 GB at 12 blocks, 18 GB with
  the phase's temporaries, over the chip's 16.9.  The compiled phase's
  memory is checked against what the chip has free first, then two
  phases of 4 inner steps and one outer sync each; losses finite, and
  each outer step moves every global leaf.

``--chips 4`` runs only ``MeshStreamingTrainer`` (``--backend mesh``):
4 workers, one path per chip, 2 outer fragments, int8 wire, then the
same phase on a (1, 1) mesh on device 0.  Depth is cut to 6 of the 12
blocks so the reference holds all four workers on one chip.  Losses
must agree within ``MESH_LOSS_TOL``, and the outer update of the global
parameters within ``MESH_UPDATE_TOL`` (relative).

Each phase prints what it ran and measured (bring-up observations, not
benchmark numbers).  The last line of standard output is one JSON
object naming the device; any failed phase raises, so the exit code is
non-zero and that line is never printed.  Without a TPU the script
exits non-zero unless ``--smoke`` asks for the CPU rehearsal, whose
last line names the CPU.
"""
from __future__ import annotations

import argparse
import functools
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent

# Limits between sound readings and planted faults (PERF.md, PR 11):
# random-init logits span about +-3, where a bf16 step is 1/64.  The
# gap carries the token check: a decode one position off still picks
# the reference's argmax 95% of the time, so agreement is a coarse guard
GAP_MAX = 0.03         # f32 logits: chosen token vs reference best
AGREE_MIN = 0.9        # share of tokens equal to the reference argmax
DECODE_TOL = 0.06      # |decode logits - f32 reference|, bf16 and int8 KV
TRAIN_LAYERS = 10      # one-chip train phase: 15.4 GB of 16.9 (v5e compile)
MESH_LOSS_TOL = 2e-2   # |loss(2x2 chips) - loss(1 chip)|
MESH_UPDATE_TOL = 5e-2  # ||g4 - g1|| / ||g1 - g0|| over all leaves


def log(msg: str) -> None:
    print(msg, flush=True)


class CacheEvents:
    """Counts persistent-compilation-cache hits and misses."""

    def __init__(self):
        import jax
        self.counts = {"cache_hits": 0, "cache_misses": 0}

        def listen(event, **kw):
            name = event.rsplit("/", 1)[-1]
            if name in self.counts:
                self.counts[name] += 1

        jax.monitoring.register_event_listener(listen)

    def take(self) -> dict:
        out = dict(self.counts)
        for k in self.counts:
            self.counts[k] = 0
        return out


def peak_bytes(dev):
    """The process's peak device bytes so far (the backend keeps one
    running peak, so each phase reports the peak up to its end)."""
    return (dev.memory_stats() or {}).get("peak_bytes_in_use")


def param_count(tree) -> int:
    import jax
    return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree))


def report(phase: str, dev, cache: CacheEvents, **fields) -> None:
    fields["peak_bytes_in_use"] = peak_bytes(dev)
    fields["compile_cache"] = cache.take()
    log(f"[{phase}] " + json.dumps(fields, default=str))


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------
def serve_args(smoke: bool, seed: int, attn_impl: str):
    from repro.launch import serve
    argv = ["--engine", "continuous", "--paths", "4", "--slots", "8",
            "--requests", "16", "--rate", "40", "--seed", str(seed),
            "--attn-impl", attn_impl]
    if smoke:
        argv += ["--smoke", "--prompt-len", "24", "--max-new", "8",
                 "--cache-len", "64"]
    else:
        argv += ["--prompt-len", "256", "--max-new", "32",
                 "--cache-len", "1024"]
    return serve.build_parser().parse_args(argv)


@functools.cache
def ref_logits_fn(cfg):
    """Jitted float32 full forward of ``cfg``'s model (f32 weights,
    matmuls and attention): the reference every served token is held to."""
    import jax
    import jax.numpy as jnp
    from repro.models import api

    cfg32 = cfg.replace(dtype="float32", attn_impl="chunked", kv_quant=False)

    @jax.jit
    def ref_logits(params, tokens):
        p32 = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), params)
        with jax.default_matmul_precision("float32"):
            return api.forward_logits(p32, cfg32, {"tokens": tokens})[0]

    return ref_logits


def token_agreement(cfg, paths, path_ids, toks, prompt_len: int,
                    pad_rows: int) -> dict:
    """Generated tokens ``toks[:, prompt_len:]`` (row i on path
    ``path_ids[i]``) against the float32 reference: the share that is
    the reference's argmax, and how far below the reference's best
    logit the chosen token is at most."""
    import jax.numpy as jnp
    ref = ref_logits_fn(cfg)
    gaps, agree = [], []
    for path in sorted(set(path_ids)):
        group = toks[np.asarray(path_ids) == path]
        # every group padded to one shape: one compile
        padded = np.zeros((pad_rows, toks.shape[1] - 1), np.int32)
        padded[:len(group)] = group[:, :-1]
        logits = np.asarray(ref(paths[path], jnp.asarray(padded)))
        # logits[:, i] predicts token i + 1; generated tokens start at
        # prompt_len
        lg = logits[:len(group), prompt_len - 1:]
        gen = group[:, prompt_len:]
        chosen = np.take_along_axis(lg, gen[..., None], axis=-1)[..., 0]
        gaps.append((lg.max(-1) - chosen).ravel())
        agree.append((lg.argmax(-1) == gen).ravel())
    gaps, agree = np.concatenate(gaps), np.concatenate(agree)
    return {"tokens": int(gaps.size), "agree_share": float(agree.mean()),
            "max_gap": float(gaps.max())}


def tokens_pass(out: dict) -> bool:
    return out["max_gap"] <= GAP_MAX and out["agree_share"] >= AGREE_MIN


def decode_tick_seconds(engine, n: int = 10) -> float:
    """Steady seconds of the dense stacked decode dispatch the engine
    issues every tick (every slot of every island), after warmup."""
    import jax
    import jax.numpy as jnp
    sa = engine._stacked_arenas
    rows = sa.num_paths * sa.num_slots
    tok = jnp.zeros((rows, 1), jnp.int32)
    mask = jnp.ones((rows,), bool)
    pos = jnp.asarray(sa.positions.reshape(-1))
    times = []
    for _ in range(n):
        t0 = time.perf_counter()
        ids, sa.cache = engine._decode_stacked(
            engine._stacked_params, tok, sa.cache, pos, mask)
        jax.block_until_ready(ids)
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def phase_serve(args, dev, cache, attn_impl: str) -> tuple:
    from repro.launch import serve
    sargs = serve_args(args.smoke, args.seed, attn_impl)
    st = serve.setup(sargs)
    run = serve.run_continuous(sargs, st)
    fins = run.finished
    if len(fins) != sargs.requests:
        raise AssertionError(f"{len(fins)} of {sargs.requests} finished")
    for f in fins:
        if len(f.tokens) != sargs.prompt_len + sargs.max_new:
            raise AssertionError(f"request {f.rid}: {len(f.tokens)} tokens")
    check = token_agreement(st.cfg, st.paths, [f.path for f in fins],
                            np.stack([f.tokens for f in fins]),
                            sargs.prompt_len, pad_rows=sargs.requests)
    if not tokens_pass(check):
        raise AssertionError(
            f"served tokens disagree with the float32 reference: {check} "
            f"(limits: max_gap <= {GAP_MAX}, agree_share >= {AGREE_MIN})")
    tick_s = decode_tick_seconds(run.engine)
    report(f"serve/{attn_impl}", dev, cache, config=st.cfg.name,
           layers=st.cfg.num_layers, d_model=st.cfg.d_model,
           vocab=st.cfg.vocab_size, dtype=st.cfg.dtype,
           params_per_path=param_count(st.paths[0]),
           paths=sargs.paths, slots=sargs.slots, cache_len=st.opts.cache_len,
           requests=sargs.requests, prompt_len=sargs.prompt_len,
           max_new=sargs.max_new, compile_s=run.compile_s,
           serve_s=run.serve_s, ticks=run.engine.ticks,
           decode_tick_s=tick_s, reference=check)
    return st.cfg, st.paths, run.trace, sargs


def greedy_tokens(cfg, params, prompts, steps: int, cache_len: int,
                  shift: int):
    """Greedy decode through ``api.prefill`` and ``api.decode_step``,
    the calls the engine makes; ``shift=1`` plants a stale-position
    fault: every step writes and attends one position early."""
    import jax
    import jax.numpy as jnp
    from repro.models import api

    rows, plen = prompts.shape
    logits, kv = jax.jit(lambda p, t: api.prefill(
        p, cfg, {"tokens": t}, cache_len))(params, prompts)
    step = jax.jit(lambda p, t, k, i: api.decode_step(
        p, cfg, {"tokens": t}, k, i))
    tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
    out = [tok]
    for n in range(steps - 1):
        pos = jnp.full((rows,), plen + n - shift, jnp.int32)
        logits, kv = step(params, tok[:, None], kv, pos)
        tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
        out.append(tok)
    return np.concatenate([np.asarray(prompts),
                           np.asarray(jnp.stack(out, 1))], 1)


def token_check_control(args, dev, cache, cfg, params, trace,
                        sargs) -> list:
    """The token check, shown to see a decode fault: greedy decode of 8
    prompts on path 0, sound and with positions one early; the sound run
    must pass the limits and the faulted one fail them."""
    import jax.numpy as jnp
    rows = 8
    prompts = jnp.asarray(np.stack([r.prompt for r in trace[:rows]]))
    cx = cfg.replace(attn_impl="chunked", kv_quant=False)
    out = {}
    for name, shift in (("sound", 0), ("position_minus_1", 1)):
        toks = greedy_tokens(cx, params, prompts, sargs.max_new,
                             sargs.cache_len, shift)
        out[name] = token_agreement(cfg, {0: params}, [0] * rows, toks,
                                    sargs.prompt_len, pad_rows=sargs.requests)
    report("token-check-control", dev, cache, rows=rows, **out)
    if not tokens_pass(out["sound"]) or tokens_pass(out["position_minus_1"]):
        return [f"the token check does not separate a sound decode from a "
                f"faulted one: {out}"]
    return []


def compare_decode(args, dev, cache, cfg, params, trace) -> list:
    """One decode step on the same cache: the XLA branch and
    ``flash_decode`` against the float32 reference at the same position,
    and two planted faults that the limit must see."""
    import jax
    import jax.numpy as jnp
    from repro.models import api

    rows = 8
    prompts = np.stack([r.prompt for r in trace[:rows]])
    plen = prompts.shape[1]
    cache_len = 64 if args.smoke else 1024
    # per-row positions: rows end their history at different places
    idx = plen - np.arange(rows) % 4
    ref = ref_logits_fn(cfg)
    out, failures = {}, []
    for kv_quant in (False, True):
        cx = cfg.replace(attn_impl="chunked", kv_quant=kv_quant)
        cp = cfg.replace(attn_impl="pallas", kv_quant=kv_quant)
        logits, kv = jax.jit(
            lambda p, t: api.prefill(p, cx, {"tokens": t}, cache_len))(
                params, jnp.asarray(prompts))
        tok = np.asarray(jnp.argmax(logits[:, -1], -1), np.int32)
        # the reference sees prompt[:i] then the new token at position i
        seq = np.concatenate([prompts, np.zeros((rows, 1), np.int32)], 1)
        seq[np.arange(rows), idx] = tok
        want = np.asarray(ref(params, jnp.asarray(seq)))[np.arange(rows), idx]
        step = {c.attn_impl: jax.jit(
            lambda p, t, k, i, c=c: api.decode_step(
                p, c, {"tokens": t}, k, i)[0][:, 0])
            for c in (cx, cp)}
        args_ = (params, jnp.asarray(tok[:, None]), kv,
                 jnp.asarray(idx, jnp.int32))
        lowered = step["pallas"].lower(*args_)
        t0 = time.perf_counter()
        compiled = lowered.compile()
        compile_s = time.perf_counter() - t0
        kernels = compiled.as_text().count("tpu_custom_call")
        if not args.smoke and kernels == 0:
            raise AssertionError("flash_decode is not a compiled TPU kernel "
                                 "in the decode step")

        def err(logits):
            return float(np.abs(np.asarray(logits, np.float32) - want).max())

        sound = {"xla": err(step["chunked"](*args_)),
                 "pallas": err(compiled(*args_))}
        faults = {"position_minus_1": err(compiled(
            *args_[:3], jnp.asarray(idx - 1, jnp.int32)))}
        if kv_quant:
            dropped = jax.tree_util.tree_map_with_path(
                lambda path, x: jnp.ones_like(x) if jax.tree_util.keystr(
                    path).endswith("['v_scale']") else x, kv)
            faults["v_scale_dropped"] = err(compiled(
                *args_[:2], dropped, args_[3]))
        out["int8" if kv_quant else "bf16"] = {
            "max_abs_err": sound, "faulted_max_abs_err": faults,
            "tpu_custom_calls": kernels, "compile_s": compile_s}
        if (max(sound.values()) > DECODE_TOL
                or min(faults.values()) <= DECODE_TOL):
            failures.append(
                f"kv_quant={kv_quant}: decode logits vs the float32 "
                f"reference {sound} should be <= {DECODE_TOL}, and the "
                f"faulted {faults} above it")
    report("decode-compare", dev, cache, rows=rows, cache_len=cache_len,
           **out)
    return failures


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------
def leaf_sums(tree):
    """``{leaf path: (sum, sum of squares)}`` fingerprints, computed on
    device."""
    import jax
    import jax.numpy as jnp
    return {jax.tree_util.keystr(path): np.asarray(jnp.stack([
        jnp.sum(x.astype(jnp.float32)),
        jnp.sum(jnp.square(x.astype(jnp.float32)))]))
        for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]}


def check_all_moved(before: dict, after: dict) -> None:
    """Every global leaf moves in every outer step."""
    still = [k for k in before if np.array_equal(before[k], after[k])]
    if still:
        raise AssertionError(f"outer step left global leaves {still}")


def train_argv(smoke: bool, extra=()):
    argv = ["--levels", "2x2", "--tau", "4", "--phases", "2"]
    if smoke:
        argv += ["--smoke", "--batch-size", "2", "--seq", "64",
                 "--docs", "64"]
    else:
        argv += ["--batch-size", "2", "--seq", "512", "--docs", "256"]
    return argv + list(extra)


def phase_train(args, dev, cache) -> None:
    import jax
    from repro.launch import train
    targs = train.build_parser().parse_args(train_argv(args.smoke))
    tr = train.build_trainer(
        targs, cfg_overrides={} if args.smoke else {"num_layers": TRAIN_LAYERS})
    tau = targs.tau
    t0 = time.perf_counter()
    compiled = tr._phase_fn.lower(tr.worker_params, tr.opt_state,
                                  *tr.phase_inputs(tau)).compile()
    compile_s = time.perf_counter() - t0
    mem = compiled.memory_analysis()
    stats = dev.memory_stats() or {}
    memory = {"argument_bytes": mem.argument_size_in_bytes,
              "temp_bytes": mem.temp_size_in_bytes,
              "alias_bytes": mem.alias_size_in_bytes}
    if "bytes_limit" in stats:
        free = stats["bytes_limit"] - stats["bytes_in_use"]
        memory.update(bytes_limit=stats["bytes_limit"],
                      bytes_in_use=stats["bytes_in_use"])
        # state is resident and donated; the phase needs its temps
        if mem.temp_size_in_bytes > free:
            raise AssertionError(f"train phase needs {memory}, "
                                 f"{free} bytes free")
    del compiled
    phases = []
    for _ in range(targs.phases):
        before = leaf_sums(tr.global_params)
        t0 = time.perf_counter()
        m = tr.run_phase()
        jax.block_until_ready(tr.global_params)
        dt = time.perf_counter() - t0
        after = leaf_sums(tr.global_params)
        losses = np.asarray(m.per_path_loss)
        if not (np.isfinite(m.mean_loss) and np.isfinite(losses).all()):
            raise AssertionError(f"non-finite losses {m}")
        check_all_moved(before, after)
        phases.append({"seconds": dt, "mean_loss": m.mean_loss,
                       "final_per_path_loss": losses.tolist(),
                       "global_leaves_moved": len(before)})
    report("train/vector", dev, cache, config=tr.cfg.name,
           layers=tr.cfg.num_layers, params_per_path=param_count(
               jax.tree_util.tree_map(lambda x: x[0], tr.worker_params)),
           workers=tr.num_workers, batch=targs.batch_size, seq=targs.seq,
           tau=tau, phase_compile_s=compile_s, memory=memory,
           phases=phases, steady_step_s=phases[-1]["seconds"] / tau)


def phase_mesh(args, dev, cache) -> None:
    """2x2 workers one per chip vs the same phase on a (1, 1) mesh."""
    import jax
    from jax.sharding import Mesh
    from repro.launch import train

    extra = ["--backend", "mesh", "--fragments", "2", "--comm-dtype", "int8",
             "--phases", "1"]
    targs = train.build_parser().parse_args(
        train_argv(args.smoke, extra))
    cut = {} if args.smoke else {"num_layers": 6}
    runs = {}
    for name, mesh in (("2x2", None),
                       ("1x1", Mesh(np.asarray(jax.devices()[:1]).reshape(
                           1, 1), ("data", "model")))):
        tr = train.build_trainer(targs, cfg_overrides=cut, mesh=mesh)
        layers = tr.cfg.num_layers
        leaf = jax.tree_util.tree_leaves(tr.worker_params)[0]
        devices = {s.device for s in leaf.addressable_shards}
        rows = {s.data.shape[0] for s in leaf.addressable_shards}
        if name == "2x2" and (len(devices) != len(jax.devices())
                              or rows != {tr.num_workers // len(devices)}):
            raise AssertionError(f"worker_params on {len(devices)} devices, "
                                 f"{rows} rows each")
        g0 = [np.asarray(x) for x in jax.tree_util.tree_leaves(
            tr.global_params)]
        t0 = time.perf_counter()
        m = tr.run_phase()
        jax.block_until_ready(tr.global_params)
        dt = time.perf_counter() - t0
        runs[name] = {
            "seconds": dt, "devices": len(devices),
            "losses": np.asarray(m.per_path_loss, np.float64),
            "mean_loss": m.mean_loss,
            "global": [np.asarray(x) for x in jax.tree_util.tree_leaves(
                tr.global_params)],
            "g0": g0, "comm": dict(tr.comm_stats)}
        if not np.isfinite(runs[name]["losses"]).all():
            raise AssertionError(f"{name}: non-finite losses {m}")
        del tr, leaf
        gc.collect()
    a, b = runs["2x2"], runs["1x1"]
    loss_diff = float(np.abs(a["losses"] - b["losses"]).max())
    num = sum(float(np.sum((x - y) ** 2)) for x, y in zip(a["global"],
                                                            b["global"]))
    den = sum(float(np.sum((y - z) ** 2)) for y, z in zip(b["global"],
                                                            b["g0"]))
    rel = (num / den) ** 0.5 if den > 0 else float("inf")
    report("train/mesh", dev, cache, layers=layers,
           workers=4, fragments=2, comm_dtype="int8", batch=targs.batch_size,
           seq=targs.seq, tau=targs.tau,
           mesh_2x2={k: a[k] for k in ("seconds", "devices", "mean_loss",
                                       "comm")},
           mesh_1x1={k: b[k] for k in ("seconds", "devices", "mean_loss")},
           max_loss_diff=loss_diff, update_rel_diff=rel)
    if not (loss_diff <= MESH_LOSS_TOL and rel <= MESH_UPDATE_TOL):
        raise AssertionError(
            f"mesh and one-chip phases disagree: loss diff {loss_diff} "
            f"(<= {MESH_LOSS_TOL}), update rel diff {rel} "
            f"(<= {MESH_UPDATE_TOL})")


# ---------------------------------------------------------------------------
def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="CPU rehearsal on the reduced preset (Pallas "
                         "interpreted); never claims a TPU")
    args = ap.parse_args()

    import jax
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu" and not args.smoke:
        print(f"chip_smoke: no TPU (JAX found {dev.platform!r}); "
              f"--smoke runs the CPU rehearsal", file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devs)} device(s)", file=sys.stderr)
        return 2

    sys.path.insert(0, str(ROOT / "src"))
    from repro.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    cache = CacheEvents()
    log(f"[device] platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devs)} jax={jax.__version__} cache={cache_dir}")

    t0 = time.perf_counter()
    if args.chips == 4:
        phase_mesh(args, dev, cache)
    else:
        # training first: its four stacked workers need most of the
        # chip, before serving has fragmented it
        phase_train(args, dev, cache)
        gc.collect()
        phase_serve(args, dev, cache, "chunked")
        gc.collect()
        cfg, paths, trace, sargs = phase_serve(args, dev, cache, "pallas")
        # both comparisons report before either limit fails the run
        failures = (compare_decode(args, dev, cache, cfg, paths[0], trace)
                    + token_check_control(args, dev, cache, cfg, paths[0],
                                          trace, sargs))
        if failures:
            raise AssertionError("; ".join(failures))
    log(f"[done] {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

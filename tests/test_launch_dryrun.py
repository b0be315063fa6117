"""Launcher coverage: the AOT dry-run's pure decision helpers
(``opt_transform`` / ``_supports``), an end-to-end ``run_case``
compile in a subprocess (the module pins the XLA host device count at
import, so it cannot share this process's jax), and the serve
launcher's argument-validation paths."""
import json
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), os.pardir, "src")


# ---------------------------------------------------------------------
# pure decision helpers — importable here because the XLA flag the
# module sets at import only takes effect at first jax init
# ---------------------------------------------------------------------

def _dryrun():
    from repro.launch import dryrun
    return dryrun


def test_opt_transform_sets_perf_flags_per_family():
    from repro.configs import ASSIGNED_ARCHS, get_config
    dr = _dryrun()
    for arch in ASSIGNED_ARCHS:
        cfg = get_config(arch)
        opt = dr.opt_transform(cfg)
        assert opt.causal_skip and opt.remat_policy == "dots"
        # decode-memory knob splits on encoder presence
        if cfg.encoder is not None:
            assert opt.cross_kv_cache and not opt.kv_quant
        else:
            assert opt.kv_quant
        # island-internal DP only below the TP crossover, never for SSM
        want_dp = cfg.d_model <= 2048 and cfg.arch_type != "ssm"
        assert (opt.island_parallelism == "data") == want_dp
        # the transform must not mutate the registry's config
        assert not cfg.causal_skip


def test_supports_long_context_notes_sliding_window():
    from repro.configs import get_config
    from repro.models.config import INPUT_SHAPES
    dr = _dryrun()
    long = INPUT_SHAPES["long_500k"]
    train = INPUT_SHAPES["train_4k"]
    ok, note = dr._supports(get_config("qwen3-8b"), long)
    assert ok and note == "sliding_window"
    for native in ("mamba2-1.3b", "jamba-v0.1-52b"):
        ok, note = dr._supports(get_config(native), long)
        assert ok and note == ""
    ok, note = dr._supports(get_config("qwen3-8b"), train)
    assert ok and note == ""


# ---------------------------------------------------------------------
# run_case end-to-end (AOT lower + compile + roofline) in a subprocess
# ---------------------------------------------------------------------

_RUN_CASE = r"""
import os, json, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
from repro.configs import get_smoke_config
from repro.launch import dryrun
from repro.models.config import INPUT_SHAPES, InputShape

INPUT_SHAPES["smoke_train"] = InputShape("smoke_train", 256, 8, "train")
dryrun.get_config = get_smoke_config            # smoke-size the archs
dryrun.make_production_mesh = (                 # 8 fake host devices
    lambda multi_pod=False: jax.make_mesh((4, 2), ("data", "model")))
recs = [dryrun.run_case("dipaco-150m", "smoke_train", multi_pod=False,
                        verbose=False, variant=v)
        for v in ("base", "opt")]
print(json.dumps(recs))
"""


@pytest.mark.slow
def test_run_case_compiles_and_rooflines_smoke_arch():
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _RUN_CASE], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    base, opt = json.loads(out.stdout.strip().splitlines()[-1])
    for rec in (base, opt):
        assert rec["ok"], rec.get("error")
        assert rec["total_flops"] > 0 and rec["total_bytes"] > 0
        assert 0 < rec["useful_flops_ratio"] <= 1
        assert rec["roofline"]["bound_s"] > 0
        assert rec["collectives"]["total_bytes"] >= 0
    # causal chunk skipping strictly raises the useful-FLOPs ratio
    assert opt["useful_flops_ratio"] > base["useful_flops_ratio"]


# ---------------------------------------------------------------------
# serve launcher argument validation
# ---------------------------------------------------------------------

def test_serve_fleet_requires_deploy_root(monkeypatch, capsys):
    from repro.launch import serve
    monkeypatch.setattr(sys, "argv", [
        "serve", "--fleet", "2", "--paths", "1", "--requests", "1"])
    with pytest.raises(SystemExit):
        serve.main()
    assert "--fleet requires --deploy-root" in capsys.readouterr().err


def test_serve_rejects_unknown_engine(monkeypatch, capsys):
    from repro.launch import serve
    monkeypatch.setattr(sys, "argv", ["serve", "--engine", "warp"])
    with pytest.raises(SystemExit):
        serve.main()
    assert "invalid choice" in capsys.readouterr().err


@pytest.mark.slow
def test_serve_oneshot_end_to_end(monkeypatch, capsys):
    from repro.launch import serve
    monkeypatch.setattr(sys, "argv", [
        "serve", "--smoke", "--paths", "2", "--requests", "2",
        "--prompt-len", "8", "--max-new", "2"])
    serve.main()
    out = capsys.readouterr().out
    assert "tok/s" in out and "request->path" in out


# ---------------------------------------------------------------------
# entry points: no fallback that hides the device, and the compile cache
# ---------------------------------------------------------------------

def test_launchers_build_the_full_config_unless_smoke():
    """On the CPU backend too: the preset is never chosen from the
    backend, and interpret mode only comes with ``--smoke``."""
    from repro.configs import get_config, get_smoke_config
    from repro.launch import serve, train
    full = serve.build_config(serve.build_parser().parse_args([]))
    want = get_config("dipaco-150m")
    assert (full.num_layers, full.d_model, full.vocab_size, full.dtype) == (
        want.num_layers, want.d_model, want.vocab_size, want.dtype)
    assert not full.pallas_interpret
    smoke = serve.build_config(serve.build_parser().parse_args(["--smoke"]))
    assert smoke.d_model == get_smoke_config("dipaco-150m").d_model
    assert smoke.pallas_interpret
    assert not train.build_parser().parse_args([]).smoke


def test_compile_cache_defers_to_the_environment(monkeypatch, tmp_path):
    import jax
    from repro.compile_cache import enable_compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    from repro.compile_cache import enable_compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = enable_compile_cache()
        want = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            os.pardir, ".jax_cache")
        assert path == os.path.realpath(want)
        assert jax.config.jax_compilation_cache_dir == path
        assert enable_compile_cache() == path      # fixed, not per call
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        compilation_cache.reset_cache()


ROOT = os.path.join(os.path.dirname(__file__), os.pardir)


def test_benchmark_run_exits_nonzero_when_a_suite_raises(monkeypatch,
                                                         capsys):
    monkeypatch.syspath_prepend(ROOT)
    from benchmarks import obs_overhead, run

    def boom(quick=True):
        raise RuntimeError("suite blew up")

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", os.devnull)
    monkeypatch.setattr(obs_overhead, "run", boom)
    monkeypatch.setattr(sys, "argv", ["run", "--only", "obs"])
    with pytest.raises(SystemExit) as exc:
        run.main()
    assert exc.value.code == 1
    assert "suite(s) raised: obs" in capsys.readouterr().err


def test_mesh_lane_refuses_an_accelerator_parent(monkeypatch):
    """The lane times forced host devices in a child; a parent holding
    an accelerator would record CPU rows as mesh rows."""
    import jax
    monkeypatch.syspath_prepend(ROOT)
    from benchmarks import outer_exec_scaling
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="forced CPU devices"):
        outer_exec_scaling._mesh_lane_rows(quick=True)

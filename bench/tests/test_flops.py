"""``bench/flops.py`` against the program's ``launch/flopmodel.py``."""
import json

import pytest

import flops
from harness import BENCH

CONFIGS = ["dipaco-150m", "dipaco-dense-1b"]


@pytest.mark.parametrize("name", CONFIGS)
@pytest.mark.parametrize("s_kv", [1, 300, 1024])
def test_token_flops_match_flopmodel(name, s_kv):
    from repro.configs import get_config
    from repro.launch.flopmodel import analyze
    from repro.models.config import InputShape
    m = json.loads((BENCH / "configs" / f"{name}.json").read_text())["model"]
    cfg = get_config(name)
    rows = 16
    want = analyze(cfg, InputShape("d", s_kv, rows, "decode")).fwd_flops
    assert flops.token_flops(m, s_kv) * rows == pytest.approx(want, rel=1e-12)


def test_prefill_is_causal_sum():
    m = json.loads((BENCH / "configs" / "dipaco-150m.json").read_text())[
        "model"]
    n = 37
    direct = sum(flops.token_flops(m, i + 1) for i in range(n))
    assert flops.prefill_flops(m, n) == pytest.approx(direct, rel=1e-12)


def test_decode_attention_work_counts_valid_positions():
    m = {"num_heads": 2, "num_kv_heads": 1, "head_dim": 4, "num_layers": 3,
         "dtype": "bfloat16"}
    f, b = flops.decode_attention_work(m, 10)
    assert f == 3 * 4 * 10 * 2 * 4
    assert b == 3 * (2 * 10 * 1 * 4 * 2 + 2 * 2 * 4 * 2)

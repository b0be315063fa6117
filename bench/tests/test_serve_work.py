"""Percentiles and the useful work rebuilt from request records."""
import math

import pytest

import flops
import serve_work
from serving import Record, ServeRun
from stats import percentile

M = {"num_layers": 2, "d_model": 8, "num_heads": 2, "num_kv_heads": 2,
     "head_dim": 4, "d_ff": 16, "vocab_size": 32, "mlp_type": "gelu",
     "dtype": "bfloat16"}


def test_percentile_nearest_rank():
    xs = list(range(1, 101))
    assert percentile(xs, 95) == 95
    assert percentile([3, 1, 2], 50) == 2
    assert percentile([1.0, float("inf")], 95) == math.inf
    assert math.isnan(percentile([], 95))


def _run():
    # r0: prompt 5, admitted at tick 1, 3 tokens -> decodes at ticks 2, 3
    # r1: prompt 2, admitted at tick 2, still in flight at the close after
    #     2 tokens -> decodes at tick 3
    # r2: never admitted
    recs = [Record(0, 0, 5, 3, 0.0, admit_tick=1, first_tick=1,
                   finish_tick=3, emitted=3),
            Record(1, 1, 2, 9, 0.1, admit_tick=2, first_tick=2, emitted=2),
            Record(2, 0, 4, 4, 0.2)]
    ticks = {1: (0.0, 1.0), 2: (1.0, 1.5), 3: (1.5, 3.0)}
    return ServeRun(model=M, records=recs,
                    tick_start={k: v[0] for k, v in ticks.items()},
                    tick_end={k: v[1] for k, v in ticks.items()},
                    decoded={1: 0, 2: 1, 3: 2}, serve={}, close=3.0,
                    seconds=1.0, open_loop=True)


def test_decode_rows_and_prefills():
    run = _run()
    assert sorted(serve_work.decode_rows(run)) == [(2, 6), (3, 3), (3, 7)]
    assert sorted(serve_work.prefills(run)) == [(1, 5), (2, 2)]


def test_mfu_counts_only_useful_work():
    run = _run()
    want = (flops.prefill_flops(M, 5) + flops.prefill_flops(M, 2)
            + flops.token_flops(M, 6) + flops.token_flops(M, 3)
            + flops.token_flops(M, 7))
    assert serve_work.model_flops(run) == pytest.approx(want)
    peaks = {"bf16_flops_per_s": 1e3}
    assert serve_work.mfu(run, peaks) == pytest.approx(100 * want / 3.0 / 1e3)

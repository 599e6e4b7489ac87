"""The counts tie to the shapes the program builds."""

import jax
import pytest

from chipbench import counts, run


def _dims(path):
    return counts.model_dims(run.load_json(path))


@pytest.mark.parametrize("which", ["target", "drafter"])
@pytest.mark.parametrize("path", ["chipbench/configs/smollm-360m-135m.json",
                                  "chipbench/tests/data/tiny.json"])
def test_param_count_matches_init_params(path, which):
    from repro.models import init_params
    cfg = run.load_json(path)
    c = cfg if which == "target" else cfg["drafter"]
    mcfg = run.model_config(c, "x")
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), mcfg))
    n = sum(int(x.size) for x in jax.tree_util.tree_leaves(shapes))
    assert counts.model_dims(c).params() == n


def test_race_bytes_match_kernel_operands():
    """The fused round hands gls_row_race two (S*(L+1), K, N) f32
    operands (block_verify_batched) and gets (rows, K) f32 + i32."""
    s, k, l, n = 8, 8, 4, 49152
    c = counts.round_race(s, k, l, n)
    rows = s * (l + 1)
    operands = 2 * rows * k * n * 4
    outputs = rows * k * (4 + 4)
    assert c["bytes"] == operands + outputs
    assert c["bytes"] / c["flops"] == pytest.approx(4.0, rel=0.01)


def test_collective_bytes_match_tp_comm_model():
    from repro.configs import get_config
    from repro.launch.hlo_analysis import tp_round_comm_model
    t = get_config("granite-8b")
    d = t.replace(num_layers=2)
    want = tp_round_comm_model(t, d, num_slots=8, num_drafts=8,
                               draft_len=4, tp=4)["total_bytes"]
    dims = lambda m: counts.Dims(m.num_layers, m.d_model, m.num_heads,
                                 m.kv_heads, m.resolved_head_dim, m.d_ff,
                                 m.vocab_size)
    got = counts.tp_round_collective_bytes(dims(t), dims(d), 8, 8, 4, 4)
    assert got == pytest.approx(want, rel=1e-12)
    assert counts.tp_round_collective_bytes(dims(t), dims(d), 8, 8, 4,
                                            1) == 0.0


def test_prefill_flops_closed_form():
    m = _dims("chipbench/tests/data/tiny.json")
    want = sum(counts.token_flops(m, p + 1) for p in range(37))
    assert counts.prefill_flops(m, 37) == pytest.approx(want, rel=1e-12)


def test_round_flops_counts_live_slots_only():
    m = _dims("chipbench/tests/data/tiny.json")
    one = counts.round_flops(m, m, 4, 2, [10])
    assert counts.round_flops(m, m, 4, 2, [10, 10]) == 2 * one
    assert counts.round_flops(m, m, 4, 2, []) == 0.0

"""The serving launcher's construction (``launch/serve.py``): argument
checks, seeded random pairs and prompts, and ``build_server`` serving
the same tokens through the Pallas verifier, the XLA verifier and the
paged arena — the one-chip smoke's contract, at toy widths on the CPU."""

import jax
import numpy as np
import pytest

from repro.launch import serve
from repro.models import ModelConfig, init_params

TCFG = ModelConfig(name="t", family="dense", num_layers=2, d_model=64,
                   num_heads=4, num_kv_heads=2, head_dim=16, d_ff=128,
                   vocab_size=96, dtype="float32")
DCFG = TCFG.replace(name="d", num_layers=1)
BASE = ["--target", "smollm-360m", "--drafter", "smollm-135m",
        "--cache-mode", "kv_fused", "--drafts", "4", "--draft-len", "3",
        "--max-batch", "4", "--max-new", "8"]


def test_target_and_drafter_go_together():
    with pytest.raises(SystemExit):
        serve.parse_args(["--target", "smollm-360m"])
    args = serve.parse_args(["--target", "smollm-360m", "--drafter",
                             "smollm-135m"])
    assert (args.target, args.drafter, args.init_seed) == \
        ("smollm-360m", "smollm-135m", 0)


def test_random_prompts_are_seeded_and_bounded():
    a = serve.random_prompts(8, 96, seed=3)
    b = serve.random_prompts(8, 96, seed=3)
    assert [p.tolist() for p in a] == [p.tolist() for p in b]
    assert all(16 <= len(p) <= 128 for p in a)
    assert all(p.min() >= 1 and p.max() < 96 for p in a)
    assert [p.tolist() for p in serve.random_prompts(8, 96, seed=4)] != \
        [p.tolist() for p in a]


def test_init_served_params_sharded_equals_unsharded():
    """Weights made already sharded (the tp path) hold the values of
    the unsharded init: the RNG stream does not depend on placement."""
    from repro.launch.mesh import make_tp_mesh
    key = jax.random.PRNGKey(5)
    plain = serve.init_served_params(TCFG, key)
    sharded = serve.init_served_params(TCFG, key, mesh=make_tp_mesh(1))
    assert jax.tree.structure(plain) == jax.tree.structure(
        init_params(key, TCFG))
    for a, b in zip(jax.tree.leaves(plain), jax.tree.leaves(sharded)):
        assert b.sharding.mesh.axis_names == ("model",)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_build_server_paths_agree():
    """pallas == xla == paged through ``build_server``: every request
    completes with in-vocabulary tokens, no draft sync, one host sync
    per round."""
    pair = serve.random_pair(TCFG, DCFG, seed=0)
    prompts = serve.random_prompts(4, TCFG.vocab_size, seed=0, max_len=40)
    outs = {}
    for name, extra in (("pallas", ["--backend", "pallas"]),
                        ("xla", ["--backend", "xla"]),
                        ("paged", ["--backend", "pallas", "--paged"])):
        server = serve.build_server(serve.parse_args(BASE + extra), pair)
        for p in prompts:
            server.submit(p, max_new=8)
        done = server.run(jax.random.PRNGKey(0))
        m = server.metrics
        assert len(done) == len(prompts) and not server.failed
        assert m.draft_syncs == 0 and m.host_syncs == m.rounds
        outs[name] = [list(r.output)
                      for r in sorted(done, key=lambda r: r.uid)]
        assert all(len(o) == 8 for o in outs[name])
        assert all(0 <= t < TCFG.vocab_size for o in outs[name] for t in o)
    assert outs["pallas"] == outs["xla"] == outs["paged"]

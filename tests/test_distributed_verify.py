"""specdec/distributed.py is the serving engine's TP layer (DESIGN.md
§15): ``tp_round_specs``/``tp_fused_round`` shard the REAL fused round
(``CachedSpecDecEngine`` builds its tp>1 program through them), and the
vocab-sharded GLS race collective must match the single-device race
exactly.  Multi-device cases run in a subprocess (device count is
locked at first jax init, so they need their own process with
XLA_FLAGS) unless the session already has 8+ devices (the CI
sharded-smoke job)."""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType
from jax.sharding import PartitionSpec as P

from repro.kernels.gls_race.ref import gls_race_ref
from repro.specdec.distributed import make_sharded_gls_verify, tp_round_specs


def _check(mesh):
    k, n = 4, 256
    key = jax.random.PRNGKey(0)
    ku, kq = jax.random.split(key)
    log_u = jnp.log(jax.random.uniform(ku, (k, n), minval=1e-30, maxval=1.0))
    q = jax.random.dirichlet(kq, jnp.ones(n), (k,))
    active = jnp.asarray([True, True, False, True])
    verify = make_sharded_gls_verify(mesh)
    with mesh:
        x, y = verify(log_u, q, active)
    log_s = jnp.log(-log_u)
    xr, yr = gls_race_ref(log_s[None], jnp.log(q)[None], jnp.log(q)[None],
                          active[None])
    np.testing.assert_array_equal(np.asarray(x), np.asarray(xr[0]))
    assert int(y) == int(yr[0])


def test_sharded_verify_single_device():
    mesh = jax.make_mesh((1,), ("model",), axis_types=(AxisType.Auto,))
    _check(mesh)


def test_sharded_verify_eight_devices_subprocess():
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys
        sys.path.insert(0, "src")
        sys.path.insert(0, "tests")
        import jax
        from jax.sharding import AxisType
        from test_distributed_verify import _check
        mesh = jax.make_mesh((8,), ("model",), axis_types=(AxisType.Auto,))
        _check(mesh)
        print("SHARDED_OK")
    """)
    env = {**os.environ, "PYTHONPATH": "src"}
    env.pop("XLA_FLAGS", None)  # the script pins its own device count
    out = subprocess.run([sys.executable, "-c", script], cwd=".",
                         capture_output=True, text=True, timeout=300,
                         env=env)
    assert "SHARDED_OK" in out.stdout, out.stderr[-2000:]


# ---------------------------------------------------------------------------
# The serving-path specs: what the fused round actually shards with.
# ---------------------------------------------------------------------------


def test_tp_round_specs_serving_layout():
    """The fused round's shard_map specs implement the exact-collective
    layout: every matmul weight (including wo/w_down, which the
    TRAINING rules row-shard) on its OUTPUT dim, KV arenas on the heads
    axis, all control state and the packed result replicated — the
    one-transfer-per-round contract is encoded right here."""
    from repro.models import ModelConfig, init_params

    cfg = ModelConfig(name="t", family="dense", num_layers=2, d_model=64,
                      num_heads=8, num_kv_heads=4, head_dim=16, d_ff=128,
                      vocab_size=64, dtype="float32")
    params = init_params(jax.random.PRNGKey(0), cfg)
    mesh = jax.make_mesh((1,), ("model",), axis_types=(AxisType.Auto,))
    arena = {"k": jnp.zeros((2, 8, 4, 32, 16)),
             "v": jnp.zeros((2, 8, 4, 32, 16))}
    in_specs, out_specs = tp_round_specs(params, params, arena, arena, mesh)
    t_spec = in_specs[0]
    layer = t_spec["layers"]
    for w in ("wq", "wk", "wv", "wo"):
        assert layer["attn"][w] == P(None, None, "model"), w
    for w in ("w_gate", "w_up", "w_down"):
        assert layer["mlp"][w] == P(None, None, "model"), w
    assert t_spec["lm_head"] == P(None, "model")
    assert t_spec["embed"] == P(None, None)          # replicated
    assert t_spec["final_norm"]["scale"] == P(None)
    kv = P(None, None, "model", None, None)
    assert in_specs[2] == {"k": kv, "v": kv}
    assert in_specs[4:] == (P(), P(), P(), P())      # pos/pending/live/subs
    assert out_specs[0] == {"k": kv, "v": kv}
    assert all(s == P() for s in out_specs[3].values())  # packed replicated


def test_serving_path_through_distributed_subprocess():
    """End-to-end: a tp=2 CachedSpecDecEngine (whose fused round is
    built through tp_round_specs/tp_fused_round) emits exactly the
    tp=1 token stream.  The full tp x strategy battery lives in
    tests/test_sharded_round.py; this pins the distributed.py wiring."""
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys
        sys.path.insert(0, "src")
        import jax
        import numpy as np
        from repro.models import ModelConfig, init_params
        from repro.specdec import CachedSpecDecEngine, SpecDecConfig
        TCFG = ModelConfig(name="t", family="dense", num_layers=2,
                           d_model=64, num_heads=8, num_kv_heads=4,
                           head_dim=16, d_ff=128, vocab_size=64,
                           dtype="float32")
        DCFG = TCFG.replace(name="d", num_layers=1)
        tp = init_params(jax.random.PRNGKey(0), TCFG)
        dp = init_params(jax.random.PRNGKey(1), DCFG)
        prompt = np.array([1, 2, 3, 4], np.int32)
        outs = {}
        for n in (1, 2):
            sd = SpecDecConfig(num_drafts=4, draft_len=3, strategy="gls",
                               max_new_tokens=10, top_k=0, tp=n)
            eng = CachedSpecDecEngine((tp, TCFG), (dp, DCFG), sd)
            assert (eng.mesh is not None) == (n > 1)
            outs[n] = eng.generate(jax.random.PRNGKey(5), prompt,
                                   fused=True).output
        np.testing.assert_array_equal(outs[1], outs[2])
        print("TP_SERVE_OK")
    """)
    env = {**os.environ, "PYTHONPATH": "src"}
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", script], cwd=".",
                         capture_output=True, text=True, timeout=560,
                         env=env)
    assert "TP_SERVE_OK" in out.stdout, out.stderr[-2000:]

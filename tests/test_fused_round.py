"""Fused single-dispatch rounds (DESIGN.md §8): the kv_fused path must
be BIT-identical to the host-driven kv path — and, through it, to the
sequential reference scheduler — across all six verification strategies
and both device verifier backends, while spending zero draft syncs and
exactly one host sync per round."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import ModelConfig, init_params
from repro.specdec import (
    STRATEGIES,
    CachedSpecDecEngine,
    SpecDecConfig,
    SpecDecEngine,
    SpecDecServer,
)
from repro.specdec import engine_cached

TCFG = ModelConfig(name="t", family="dense", num_layers=3, d_model=64,
                   num_heads=4, num_kv_heads=2, head_dim=16, d_ff=128,
                   vocab_size=64, dtype="float32")
DCFG = TCFG.replace(name="d", num_layers=1)


@pytest.fixture(scope="module")
def pair():
    return (init_params(jax.random.PRNGKey(0), TCFG),
            init_params(jax.random.PRNGKey(1), DCFG))


def _generate_both(pair, strategy, backend, runs=2, max_new=14):
    """(kv output, fused output) per run, identical shared randomness."""
    tp, dp = pair
    k = 1 if strategy in ("single", "daliri") else 4
    sd = SpecDecConfig(num_drafts=k, draft_len=3, strategy=strategy,
                       max_new_tokens=max_new, top_k=0,
                       verifier_backend=backend)
    prompt = np.array([1, 2, 3, 4], np.int32)
    outs = []
    for i in range(runs):
        key = jax.random.PRNGKey(50 + i)
        kv = CachedSpecDecEngine((tp, TCFG), (dp, DCFG), sd)
        fz = CachedSpecDecEngine((tp, TCFG), (dp, DCFG), sd)
        outs.append((kv.generate(key, prompt).output,
                     fz.generate(key, prompt, fused=True).output))
    return outs


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_fused_round_bit_identical_to_kv(pair, strategy):
    """The hard contract: fusing the round into one dispatch changes
    dispatch count and sync count, never tokens — exact equality, every
    strategy."""
    for kv_out, fz_out in _generate_both(pair, strategy, "xla"):
        np.testing.assert_array_equal(kv_out, fz_out)


@pytest.mark.slow
@pytest.mark.parametrize("strategy", STRATEGIES)
def test_fused_round_bit_identical_to_kv_pallas(pair, strategy):
    """Nightly sweep: same exactness with the batched gls_race row
    kernel standing in for the xla race reduction."""
    for kv_out, fz_out in _generate_both(pair, strategy, "pallas"):
        np.testing.assert_array_equal(kv_out, fz_out)


def test_fused_scheduler_bit_identical_to_sequential_reference(pair):
    """kv_fused through the scheduler == the sequential re-prefill
    reference trace (the DESIGN.md §1 layering contract, extended)."""
    tp, dp = pair
    sd = SpecDecConfig(num_drafts=2, draft_len=2, strategy="gls", top_k=0)
    outs = {}
    for mode in ("reprefill", "kv_fused"):
        if mode == "reprefill":
            eng = SpecDecEngine((tp, TCFG), [(dp, DCFG)], sd)
        else:
            eng = CachedSpecDecEngine((tp, TCFG), (dp, DCFG), sd,
                                      pool_slots=2)
        server = SpecDecServer(eng, max_batch=2, cache_mode=mode)
        for _ in range(5):
            server.submit(np.array([1, 2, 3], np.int32), max_new=6)
        done = server.run(jax.random.PRNGKey(7))
        outs[mode] = {r.uid: list(r.output) for r in done}
    assert outs["kv_fused"] == outs["reprefill"]


def test_fused_sync_accounting(pair):
    """DESIGN.md §7.3 (revised): a fused round spends ZERO draft syncs
    (tokens never leave the device mid-round) and exactly ONE host sync
    (the packed result fetch) — so over a server trace,
    draft_syncs == 0 and host_syncs == rounds."""
    tp, dp = pair
    sd = SpecDecConfig(num_drafts=4, draft_len=3, strategy="gls", top_k=0)
    eng = CachedSpecDecEngine((tp, TCFG), (dp, DCFG), sd, pool_slots=2)
    server = SpecDecServer(eng, max_batch=2, cache_mode="kv_fused")
    for _ in range(3):
        server.submit(np.array([1, 2, 3], np.int32), max_new=8)
    server.run(jax.random.PRNGKey(3))
    m = server.metrics
    assert m.rounds > 0
    assert m.draft_syncs == 0
    assert m.host_syncs == m.rounds
    # ONE stacked verify per round on the target side too.
    assert m.target_forwards == m.rounds
    assert eng.num_draft_syncs == 0


def test_fused_generate_sync_accounting(pair):
    """Single-request accounting: host_syncs == blocks (R=1 rounds)."""
    tp, dp = pair
    sd = SpecDecConfig(num_drafts=4, draft_len=3, strategy="gls",
                       max_new_tokens=16, top_k=0)
    eng = CachedSpecDecEngine((tp, TCFG), (dp, DCFG), sd)
    o = eng.generate(jax.random.PRNGKey(3), np.array([1, 2, 3], np.int32),
                     fused=True)
    assert o.host_syncs == o.blocks
    assert eng.num_draft_syncs == 0


def test_fused_rejects_legacy_backend(pair):
    """The legacy verifier is a host loop — it cannot run inside the
    fused program and must fail loudly, not silently fall back."""
    tp, dp = pair
    sd = SpecDecConfig(num_drafts=2, draft_len=2, strategy="gls", top_k=0,
                       verifier_backend="legacy")
    eng = CachedSpecDecEngine((tp, TCFG), (dp, DCFG), sd)
    with pytest.raises(ValueError, match="legacy"):
        eng.generate(jax.random.PRNGKey(0), np.array([1, 2, 3], np.int32),
                     fused=True)


def test_fused_multi_request_matches_solo(pair):
    """Slot isolation survives fusion: two co-resident fused requests
    emit exactly what each emits alone in a one-slot pool."""
    tp, dp = pair
    sd = SpecDecConfig(num_drafts=2, draft_len=2, strategy="gls", top_k=0)
    prompts = {7: np.array([1, 2, 3], np.int32),
               9: np.array([4, 5, 6, 7], np.int32)}
    max_new = 8
    buf = max(len(p) for p in prompts.values()) + max_new + 4

    def drive(engine, uids):
        out = {u: [] for u in uids}
        prefix = {u: list(prompts[u]) for u in uids}
        blocks = {u: 0 for u in uids}
        while any(len(out[u]) < max_new for u in uids):
            live = [u for u in uids if len(out[u]) < max_new]
            subs = [jax.random.fold_in(jax.random.PRNGKey(11), u * 100
                                       + blocks[u]) for u in live]
            res = engine.gen_blocks(
                subs, [np.asarray(prefix[u], np.int32) for u in live],
                buf, uids=live, fused=True)
            for u, o in zip(live, res):
                out[u].extend(o.new_tokens)
                prefix[u].extend(o.new_tokens)
                blocks[u] += 1
                if len(out[u]) >= max_new:
                    engine.release(u)
        return {u: out[u][:max_new] for u in uids}

    multi = CachedSpecDecEngine((tp, TCFG), (dp, DCFG), sd, pool_slots=2)
    both = drive(multi, [7, 9])
    assert multi.pool.num_free == 2
    for u in (7, 9):
        solo = CachedSpecDecEngine((tp, TCFG), (dp, DCFG), sd,
                                   pool_slots=1)
        assert drive(solo, [u]) == {u: both[u]}, f"uid {u} diverged"


# ---------------------------------------------------------------------------
# windowed rollback (DESIGN.md §8): the round copies only the surviving
# row's L+1 written positions, against the whole-row gather as oracle
# ---------------------------------------------------------------------------

def _take_rollback(arenas, row_src, starts, m):
    """The whole-arena surviving-row gather the windowed copy replaced:
    every row becomes its source row everywhere (the round passes each
    row of a slot not in ``live`` itself as its source)."""
    return jax.tree.map(lambda leaf: jnp.take(leaf, row_src, axis=1),
                        arenas)


@pytest.mark.parametrize("dtype", ["float32", "int8"])
def test_replicate_windows_copies_only_the_window(dtype):
    """Rows take their source row's window and keep everything else, in
    every leaf; rows that are their own source stay bit-for-bit, even
    where their window clamps at the buffer's end."""
    ly, rows, h, t, d, m = 2, 6, 3, 11, 4, 3
    key = jax.random.PRNGKey(0)
    arenas = ({"k": jax.random.randint(key, (ly, rows, h, t, d), -100,
                                       100).astype(dtype)},
              {"k": jax.random.randint(key, (ly + 1, rows, 1, t, 1), -100,
                                       100).astype(dtype)})
    row_src = jnp.array([1, 1, 1, 5, 4, 5], jnp.int32)
    starts = jnp.array([2, 2, 2, 8, 9, 0], jnp.int32)
    out = engine_cached._replicate_windows(arenas, row_src, starts, m)
    for leaf, got in zip(jax.tree.leaves(arenas), jax.tree.leaves(out)):
        want = np.asarray(leaf).copy()
        for r in range(rows):
            p, src = int(starts[r]), int(row_src[r])
            want[:, r, :, p:p + m] = np.asarray(leaf)[:, src, :, p:p + m]
        np.testing.assert_array_equal(np.asarray(got), want)
        np.testing.assert_array_equal(np.asarray(got)[:, 4],
                                      np.asarray(leaf)[:, 4])


SLOTS, K_W, L_W, BUF_W = 4, 4, 3, 24


def _arena(key, cfg, quant):
    """Random arena content, so that every position a rollback may not
    touch holds bytes of its own."""
    shape = (cfg.num_layers, SLOTS * K_W, cfg.kv_heads, BUF_W,
             cfg.resolved_head_dim)
    ks = jax.random.split(key, 4)
    if not quant:
        return {"k": jax.random.normal(ks[0], shape),
                "v": jax.random.normal(ks[1], shape)}
    return {"k": jax.random.randint(ks[0], shape, -127, 128).astype(
                jnp.int8),
            "v": jax.random.randint(ks[1], shape, -127, 128).astype(
                jnp.int8),
            "k_s": jax.random.uniform(ks[2], shape[:-1] + (1,), minval=0.01,
                                      maxval=0.05),
            "v_s": jax.random.uniform(ks[3], shape[:-1] + (1,), minval=0.01,
                                      maxval=0.05)}


def _equalize_prefix(arena, pos, live):
    """The window invariant the round relies on: every row of a live
    slot equals the slot's first row on [0, pos)."""
    out = {}
    for kk, leaf in arena.items():
        leaf = np.asarray(leaf).copy()
        for s in np.flatnonzero(live):
            r0, p = s * K_W, int(pos[s])
            leaf[:, r0 + 1:r0 + K_W, :, :p] = leaf[:, r0:r0 + 1, :, :p]
        out[kk] = jnp.asarray(leaf)
    return out


@pytest.mark.parametrize("setup", ["drafter", "self_draft", "quant"])
def test_windowed_rollback_matches_whole_row_gather(pair, setup,
                                                    monkeypatch):
    """Chained fused rounds against the same rounds with the whole-row
    gather: equal packed results and positions; on live rows the
    arenas agree with the oracle's on all the round wrote and every
    earlier position (past that each row keeps its own garbage, beyond
    every kv_len); rows of slots not advancing are exactly as the
    oracle leaves them, and outside the round's [pos, pos + L] as they
    came in.  After each round all K rows of a live slot agree on
    [0, pos + L], which holds [0, new_pos).  One live slot's window
    ends exactly at buf_len; the rounds accept no draft, some, and
    all."""
    tp, dp = pair
    t_cfg, d_cfg = TCFG, DCFG
    if setup == "self_draft":
        dp, d_cfg = tp, TCFG
    quant = setup == "quant"
    sd = SpecDecConfig(num_drafts=K_W, draft_len=L_W, strategy="gls",
                       top_k=0)
    core = engine_cached.build_round_core(sd, t_cfg, d_cfg,
                                          t_cfg.vocab_size, SLOTS)
    # Slot 0 live from 4; slot 1 live with its window ending at buf_len,
    # then released; slot 2 occupied but unlisted; slot 3 free.
    pos = np.array([4, BUF_W - L_W - 1, 6, 0], np.int32)
    live = np.array([True, True, False, False])
    pending = np.array([5, 9, 0, 0], np.int32)
    t_kv = _equalize_prefix(_arena(jax.random.PRNGKey(3), t_cfg, quant),
                            pos, live)
    d_kv = _equalize_prefix(_arena(jax.random.PRNGKey(4), d_cfg, quant),
                            pos, live)
    args = (tp, dp, t_kv, d_kv, jnp.asarray(pos), jnp.asarray(pending),
            jnp.asarray(live), jax.random.split(jax.random.PRNGKey(60),
                                                SLOTS))
    new = jax.jit(core).lower(*args).compile()
    with monkeypatch.context() as mp:
        mp.setattr(engine_cached, "_replicate_windows", _take_rollback)
        oracle = jax.jit(engine_cached.build_round_core(
            sd, t_cfg, d_cfg, t_cfg.vocab_size, SLOTS)).lower(*args).compile()

    state = {"new": (t_kv, d_kv), "oracle": (t_kv, d_kv)}
    accepted = set()
    for rnd in range(4):
        subs = jax.random.split(jax.random.PRNGKey(60 + rnd), SLOTS)
        outs = {}
        for name, prog in (("new", new), ("oracle", oracle)):
            t_in, d_in = state[name]
            outs[name] = prog(tp, dp, t_in, d_in, jnp.asarray(pos),
                              jnp.asarray(pending), jnp.asarray(live), subs)
        (t_n, d_n, pos_n, pk_n), (t_o, d_o, pos_o, pk_o) = (outs["new"],
                                                          outs["oracle"])
        np.testing.assert_array_equal(pos_n, pos_o)
        for kk in pk_n:
            np.testing.assert_array_equal(pk_n[kk], pk_o[kk], err_msg=kk)
        for model, a_in, a_n, a_o in (("target", state["new"][0], t_n, t_o),
                                      ("drafter", state["new"][1], d_n, d_o)):
            for kk in a_n:
                x_in, x_n, x_o = (np.asarray(a_in[kk]), np.asarray(a_n[kk]),
                                  np.asarray(a_o[kk]))
                for s in range(SLOTS):
                    rows = slice(s * K_W, (s + 1) * K_W)
                    hi = int(pos[s]) + L_W + 1
                    if live[s]:
                        # The drafter writes pos + L only on full
                        # acceptance (the catch-up); else it is garbage
                        # the next round's sweep overwrites first.
                        agree = hi if model == "target" else max(
                            hi - 1, int(pos_n[s]))
                        np.testing.assert_array_equal(
                            x_n[:, rows, :, :agree], x_o[:, rows, :, :agree],
                            err_msg=f"round {rnd} {model} {kk} slot {s}")
                        win = x_n[:, rows, :, :hi]
                        np.testing.assert_array_equal(
                            win, np.broadcast_to(win[:, :1], win.shape),
                            err_msg=f"round {rnd} {model} {kk} slot {s}")
                    else:
                        np.testing.assert_array_equal(x_n[:, rows],
                                                      x_o[:, rows])
                        keep = np.ones(BUF_W, bool)
                        keep[int(pos[s]):hi] = False
                        np.testing.assert_array_equal(
                            x_n[:, rows][:, :, :, keep],
                            x_in[:, rows][:, :, :, keep])
        acc = np.asarray(pk_n["accepted"])
        accepted.update(int(a) for a in acc[live])
        pending = np.asarray(pk_n["tokens"])[np.arange(SLOTS), acc]
        pending = np.where(live, pending, 0).astype(np.int32)
        pos = np.asarray(pos_n).copy()
        state = {"new": (t_n, d_n), "oracle": (t_o, d_o)}
        if rnd == 0:
            # Slot 1 filled its buffer: released (the pool zeroes pos).
            live[1], pos[1] = False, 0
    assert {0, L_W} <= accepted and accepted - {0, L_W}, accepted

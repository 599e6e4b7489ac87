"""Plain reference of the served output, independent of the program.

It imports nothing of the program and takes nothing it made.  From the
seed it draws the same random weights the serving launcher draws
(``jax.random`` is a pure function of its key: the keys are split as
the launcher splits them and each weight is drawn with the same
distribution and shape), runs the llama forward pass of the target and
of the drafter over a prompt and its served tokens, and judges every
served token by the GLS race (the paper's Gumbel-max list sampling)
that chose it.

The race: the round of request ``uid`` that emitted its ``b``-th block
draws log-uniforms ``log U`` of shape (L+1, K, N) from
``fold_in(fold_in(key, uid), b)``.  At block position j each lane k
races ``log(-log U[j, k, v]) - log q(v)`` over the vocabulary, q being
the target's temperature-1 top-k distribution after the emitted prefix;
the token emitted at j is the winner of the lanes still active.  All K
lanes are active at j = 0; lane k stays active past j while its draft
at j (the drafter's own race on the same ``log U[j, k]`` over its top-k
distribution, which for an active lane follows the emitted prefix too)
equals the emitted token.  A block ends at j < L exactly when no lane
stays active, and after j = L (the bonus token).

A token's ``gap`` is how far its race time over the active lanes lies
above the reference's best (in nats; 0 when they agree), or, where it
lies outside the reference's top-k set, how far its logit lies below
the k-th largest, whichever is larger.  A token at which the reference
has no active lane left reads ``inf``, and so does the last token of a
block (other than the request's last) after which a lane would have
stayed active.  A sound program differs from the reference only by
rounding, so its gaps are rounding-sized; a token altered anywhere on
the path, a draft accepted that should not be, or one rejected that
should not be, reads a gap of order one or ``inf``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.counts import Dims

HIGHEST = jax.lax.Precision.HIGHEST


def _normal(key, shape, scale):
    return jax.random.normal(key, shape, jnp.float32) * scale


def _dense(key, fan_in, fan_out):
    return _normal(key, (fan_in, fan_out), 1.0 / jnp.sqrt(fan_in))


def init_weights(key, m: Dims) -> dict:
    """The launcher's random llama weights for ``key`` (norm scales are
    all ones there, so they are left out here)."""
    k_embed, k_layers, k_head = jax.random.split(key, 3)
    d, hd = m.d_model, m.head_dim

    def layer(k):
        k1, k2 = jax.random.split(k)
        ka = jax.random.split(k1, 4)
        km = jax.random.split(k2, 3)
        return {"wq": _dense(ka[0], d, m.heads * hd),
                "wk": _dense(ka[1], d, m.kv_heads * hd),
                "wv": _dense(ka[2], d, m.kv_heads * hd),
                "wo": _dense(ka[3], m.heads * hd, d),
                "w_gate": _dense(km[0], d, m.d_ff),
                "w_up": _dense(km[1], d, m.d_ff),
                "w_down": _dense(km[2], m.d_ff, d)}

    return {"embed": _normal(k_embed, (m.padded_vocab, d), 0.02),
            "layers": jax.vmap(layer)(jax.random.split(k_layers, m.layers)),
            "lm_head": _dense(k_head, d, m.padded_vocab)}


def pair_keys(seed: int):
    """(target key, drafter key) as the launcher splits them."""
    kt, kd = jax.random.split(jax.random.PRNGKey(seed))
    return kt, kd


def _rmsnorm(x, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return y.astype(x.dtype)


def _rope(x, theta):
    """x: (T, H, D); rotate-half RoPE at positions 0..T-1."""
    t, _, dim = x.shape
    half = dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           -1).astype(x.dtype)


def forward(w: dict, tokens, *, m: Dims, eps: float, theta: float,
            precision=HIGHEST, dtype=jnp.float32):
    """Logits (T, padded vocab), as float32, of one causal sequence.
    Weights, activations and the residual stream are held in ``dtype``
    (float32, or bfloat16 for the output check's control); norms, RoPE
    and softmax compute in float32."""
    P = precision
    w = jax.tree.map(lambda a: a.astype(dtype), w)
    t = tokens.shape[0]
    hd, g = m.head_dim, m.heads // m.kv_heads
    causal = jnp.tril(jnp.ones((t, t), bool))

    def block(x, lw):
        h = _rmsnorm(x, eps)
        q = jnp.dot(h, lw["wq"], precision=P).reshape(t, m.heads, hd)
        k = jnp.dot(h, lw["wk"], precision=P).reshape(t, m.kv_heads, hd)
        v = jnp.dot(h, lw["wv"], precision=P).reshape(t, m.kv_heads, hd)
        q, k = _rope(q, theta), _rope(k, theta)
        k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q, k, precision=P).astype(
            jnp.float32) / jnp.sqrt(jnp.float32(hd))
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = jnp.einsum("hqk,khd->qhd", p.astype(dtype), v, precision=P)
        x = x + jnp.dot(o.reshape(t, m.heads * hd), lw["wo"], precision=P)
        h = _rmsnorm(x, eps)
        gate = jax.nn.silu(jnp.dot(h, lw["w_gate"], precision=P))
        up = jnp.dot(h, lw["w_up"], precision=P)
        x = x + jnp.dot(gate * up, lw["w_down"], precision=P)
        return x, None

    x, _ = jax.lax.scan(block, w["embed"][tokens], w["layers"])
    return jnp.dot(_rmsnorm(x, eps), w["lm_head"],
                   precision=P).astype(jnp.float32)


def race_log_s(key, uid, block, j, l: int, k: int, vocab: int):
    """log(-log U) of position ``j`` of one block: (K, N)."""
    sub = jax.random.fold_in(jax.random.fold_in(key, uid), block)
    k_unif, _ = jax.random.split(sub)
    log_u = jnp.log(jax.random.uniform(
        k_unif, (l + 1, k, vocab), minval=np.finfo(np.float32).tiny,
        maxval=1.0))
    return jnp.log(-log_u[j])


def lane_stats(t_logits, d_logits, judged, log_s, top_k: int,
               tolerances: tuple = (0.0,)) -> dict:
    """Per position and lane, what the walk over a block needs.

    t_logits, d_logits (C, N): the target's and the drafter's logits at
    C served positions; judged (C,): the tokens judged there; log_s
    (C, K, N).  Returns, each (C, K) unless noted: ``best`` (one row per
    tolerance d, so (D, C, K)) the lane's least target race time over
    the tokens whose logit is at least the k-th largest plus d, ``arg``
    the lane's winner over the top-k, ``mine`` the judged token's race
    time in the lane, ``draft`` the lane's draft (the drafter's race
    winner over its top-k); and ``below`` (C,), how far the judged
    token's logit lies below the target's k-th largest."""
    kth = jax.lax.top_k(t_logits, top_k)[0][:, -1:]
    race = log_s - t_logits[:, None, :]
    best = jnp.stack([jnp.min(jnp.where(
        (t_logits >= kth + d)[:, None, :], race, jnp.inf), axis=2)
        for d in tolerances])
    in_t = (t_logits >= kth)[:, None, :]
    mine = jnp.take_along_axis(race, judged[:, None, None], axis=2)[..., 0]
    d_kth = jax.lax.top_k(d_logits, top_k)[0][:, -1:]
    d_race = jnp.where((d_logits >= d_kth)[:, None, :],
                       log_s - d_logits[:, None, :], jnp.inf)
    below = kth[:, 0] - jnp.take_along_axis(t_logits, judged[:, None],
                                            1)[:, 0]
    return {"best": best,
            "arg": jnp.argmin(jnp.where(in_t, race, jnp.inf),
                              axis=2).astype(jnp.int32),
            "mine": mine, "below": below,
            "draft": jnp.argmin(d_race, axis=2).astype(jnp.int32)}


def walk(stats: dict, served, blocks, l: int,
         tolerances: tuple = (0.0,)) -> tuple:
    """The GLS rule over a request's blocks, on host arrays.

    Returns (excess (D, n): for each tolerance d, by how much each judged
    token's gap exceeds what a logit error of at most d explains, the
    token the reference's race puts first at each position, accepted:
    whether the position follows an accepted draft).  A logit error of
    d moves a race time or the top-k boundary by at most d, so the gap
    a token may show is 2d against the tokens certainly in the top-k
    (logit at least the k-th largest plus d); the excess at d = 0 is
    the plain gap.  The active lanes follow the served tokens."""
    served = np.asarray(served)
    blocks = np.asarray(blocks)
    tol = 2.0 * np.asarray(tolerances, float)
    n = len(served)
    gaps = np.full((len(tol), n), np.inf)
    first = np.full(n, -1, np.int64)
    accepted = np.zeros(n, bool)
    starts = np.flatnonzero(np.r_[True, blocks[1:] != blocks[:-1]])
    ends = np.r_[starts[1:], n]
    for b, (s, e) in enumerate(zip(starts, ends)):
        active = np.ones(stats["arg"].shape[1], bool)
        accepted[s + 1:e] = True
        for j, i in enumerate(range(s, e)):
            if j > l or not active.any():
                break
            best = np.where(active, stats["best"][:, i], np.inf)  # (D, K)
            first[i] = stats["arg"][i][np.argmin(best[0])]
            mine = np.min(np.where(active, stats["mine"][i], np.inf))
            gaps[:, i] = np.maximum(mine - best.min(axis=1),
                                    stats["below"][i]) - tol
            if j < l:
                active &= stats["draft"][i] == served[i]
        last_block = b == len(starts) - 1
        if not last_block and e - s - 1 < l and active.any():
            gaps[:, e - 1] = np.inf    # the block should have gone on
    return gaps, first, accepted


class Reference:
    """The reference pair for one configuration and seed.

    ``stats(prompt, served, blocks, uid, judged)`` gives ``lane_stats``
    at every served position (the context is the prompt and the served
    tokens; ``judged`` defaults to the served tokens).  One padded
    sequence length, so each forward pass compiles once."""

    def __init__(self, t_dims: Dims, d_dims: Dims, weight_keys, race_key,
                 *, t_norm: tuple, d_norm: tuple, k: int, l: int,
                 top_k: int, seq_len: int, chunk: int = 16,
                 precision=HIGHEST, dtype=jnp.float32,
                 tolerances: tuple = (0.0,)):
        self.k, self.l, self.top_k = k, l, top_k
        self.tolerances = tuple(tolerances)
        self.vocab = t_dims.vocab
        self.seq_len, self.chunk = seq_len, chunk
        self.race_key = race_key
        kt, kd = weight_keys
        self.wt = jax.jit(functools.partial(init_weights, m=t_dims))(kt)
        self.wd = jax.jit(functools.partial(init_weights, m=d_dims))(kd)

        def fwd(m, norm):
            return jax.jit(functools.partial(
                forward, m=m, eps=norm[0], theta=norm[1],
                precision=precision, dtype=dtype))
        self._fwd_t, self._fwd_d = fwd(t_dims, t_norm), fwd(d_dims, d_norm)
        self._stats = jax.jit(self._chunk_stats)

    def _chunk_stats(self, t_logits, d_logits, judged, uid, blocks, js):
        n = self.vocab
        log_s = jax.vmap(lambda b, j: race_log_s(
            self.race_key, uid, b, j, self.l, self.k, n))(blocks, js)
        return lane_stats(t_logits[:, :n], d_logits[:, :n], judged, log_s,
                          self.top_k, self.tolerances)

    def stats(self, prompt, served, blocks, uid, judged=None) -> dict:
        prompt = np.asarray(prompt, np.int32)
        served = np.asarray(served, np.int32)
        judged = served if judged is None else np.asarray(judged, np.int32)
        blocks = np.asarray(blocks, np.int64)
        seq = np.concatenate([prompt, served[:-1]])
        assert len(seq) <= self.seq_len, (len(seq), self.seq_len)
        pad = np.zeros(self.seq_len, np.int32)
        pad[:len(seq)] = seq
        lt = self._fwd_t(self.wt, jnp.asarray(pad))
        ld = self._fwd_d(self.wd, jnp.asarray(pad))
        n = len(served)
        starts = np.r_[True, blocks[1:] != blocks[:-1]]
        js = np.arange(n) - np.maximum.accumulate(
            np.where(starts, np.arange(n), 0))
        js = np.minimum(js, self.l)    # past L the walk reads nothing
        out = []
        for c in range(0, n, self.chunk):
            sel = np.resize(np.arange(c, min(c + self.chunk, n)),
                            self.chunk)          # pad the chunk by repeats
            pos = len(prompt) - 1 + sel
            got = self._stats(lt[pos], ld[pos], jnp.asarray(judged[sel]),
                              jnp.uint32(uid),
                              jnp.asarray(blocks[sel], np.uint32),
                              jnp.asarray(js[sel], np.int32))
            m = min(self.chunk, n - c)
            out.append({kk: np.asarray(v)[..., :m, :] if kk == "best"
                        else np.asarray(v)[:m] for kk, v in got.items()})
        return {kk: np.concatenate([o[kk] for o in out],
                                   axis=1 if kk == "best" else 0)
                for kk in out[0]}

"""Multi-draft speculative decoding engine (paper Sec. 4, Algorithm 2).

Design notes
------------
* Drafts and target are coupled through *common random numbers*: one block
  draws uniforms U[(L+1), K, N]; draft k samples its j-th token by the
  Gumbel race on U[j, k] and the GLS verifier races the target
  distributions on the very same sheet — this is what makes acceptance
  high AND the output conditionally drafter-invariant (Def. 1).
* Model evaluation uses fixed-size token buffers so jitted forwards
  compile once per (batch, buffer) shape: causal attention makes trailing
  garbage harmless.  The target scores all K draft continuations in one
  batched forward (the K dimension rides in the batch), matching how a
  TPU serving deployment folds drafts into the batch (DESIGN.md §3).
  The same core generalizes over R co-scheduled requests: draft buffers
  stack into (R*K, T) forwards, which is what the batched scheduler
  (scheduler.py) rides.
* Verification is FUSED: the whole L-step loop of Algorithm 2 runs as one
  jitted device program (block_verify.py) — one host transfer per block
  instead of two per token.  ``SpecDecConfig.verifier_backend`` selects
  "xla" (default), "pallas" (routes the K-way race through the
  kernels/gls_race row kernel) or "legacy" (the pre-refactor host loop,
  kept as the equivalence oracle).
* Strategies: "gls" (Alg. 2), "gls_strong" (App. B), "specinfer",
  "spectr", "single" (Leviathan), "daliri" (single-draft coupling).
  K heterogeneous drafters with per-drafter temperatures are supported
  for the paper's diverse-drafts experiment (Table 2/4).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import forward
from repro.models.config import ModelConfig
from repro.specdec import verify as V
from repro.specdec.block_verify import (
    BACKENDS,
    RS_STRATEGIES,
    run_block_verify,
)

STRATEGIES = ("gls", "gls_strong", "specinfer", "spectr", "single", "daliri")


@dataclasses.dataclass(frozen=True)
class SpecDecConfig:
    num_drafts: int = 8           # K
    draft_len: int = 4            # L
    strategy: str = "gls"
    target_temp: float = 1.0
    draft_temps: Optional[tuple] = None   # per-drafter; default all 1.0
    top_k: int = 50               # paper uses top-K 50 sampling
    max_new_tokens: int = 64
    verifier_backend: str = "xla"  # "legacy" | "xla" | "pallas"
    # Tri-state (DESIGN.md §11): None autodetects — compiled Pallas on
    # TPU/GPU, the bit-identical jnp fallback elsewhere; True forces the
    # interpreter (kernel body on any backend); False forces compiled.
    pallas_interpret: Optional[bool] = None
    # Route the cached engine's slot-aware decode attention through the
    # kernels/decode_attention Pallas kernel.  Numerically equivalent
    # but NOT bit-equal to the dense path (online-softmax reduction
    # order), so it defaults off wherever bit-identity contracts apply.
    decode_kernel: bool = False
    # Route the cached engine's admission prefill chunks through the
    # kernels/flash_attention Pallas kernel (the causal multi-token
    # use_kernel route of layers.attention).  Same opt-in contract as
    # decode_kernel: numerically equivalent, not bit-equal.
    prefill_kernel: bool = False
    # Quantized serving (DESIGN.md §11): int8 KV arenas in the cached
    # engine's pool (per-vector scales, quantize-on-write) and W8A8
    # target matmuls in the fused-round verify.  Changes logits within
    # quantization tolerance, so the equivalence gate is ACCEPTANCE-RATE
    # statistics, not bit-identity (tests/test_quant_fused.py).
    quant: bool = False
    # Paged KV arena (DESIGN.md §12): the cached engine's pool stores
    # KV in fixed-size pages behind a device-resident page table
    # (models/paged.py) instead of one contiguous arena — buffer growth
    # becomes a table widening, freed requests return their pages, and
    # the scheduler's v2 policy can oversubscribe slots against a fixed
    # page budget.  Opt-in; the contiguous pool stays the bit-identity
    # oracle (all six strategies produce identical tokens either way).
    paged: bool = False
    page_size: int = 64
    # Serving tensor parallelism (DESIGN.md §15): tp > 1 runs the cached
    # engine's fused round under shard_map on a 1-D ("model",) mesh
    # (launch/mesh.make_tp_mesh) — weights output-dim sharded per
    # sharding/rules.serve_param_spec, KV arenas head-sharded — with
    # all-gather-only collectives (no psum); tokens equal tp=1 on the
    # CPU backend, but not always on a TPU (DESIGN.md §15.1).
    # Requires num_heads/kv_heads/d_ff/padded_vocab/d_model of both
    # models divisible by tp; composes with the contiguous f32 arena
    # only (the paged gather and int8 arenas stay single-device).
    tp: int = 1

    def __post_init__(self):
        if self.strategy not in STRATEGIES:
            raise ValueError(f"unknown strategy {self.strategy!r}")
        if self.verifier_backend not in BACKENDS:
            raise ValueError(
                f"unknown verifier backend {self.verifier_backend!r}")
        if self.tp < 1:
            raise ValueError(f"tp must be >= 1, got {self.tp}")
        if self.tp > 1 and self.paged:
            raise ValueError("tp > 1 requires the contiguous arena "
                             "(paged=False)")
        if self.tp > 1 and self.quant:
            raise ValueError("tp > 1 requires f32 serving (quant=False)")

    @property
    def temps(self) -> tuple:
        if self.draft_temps is not None:
            assert len(self.draft_temps) == self.num_drafts
            return tuple(self.draft_temps)
        return (1.0,) * self.num_drafts


@dataclasses.dataclass
class GenerationStats:
    output: np.ndarray            # accepted token ids
    blocks: int                   # target model calls
    accepted_drafts: int          # accepted DRAFT tokens (excl. bonus)
    host_syncs: int = 0           # device->host transfers in verification

    @property
    def block_efficiency(self) -> float:
        """Tokens emitted per target call (paper's BE metric)."""
        return len(self.output) / max(self.blocks, 1)


class BlockOutcome(NamedTuple):
    """Host-side outcome of one speculative block for one request."""
    new_tokens: list              # emitted tokens (num_accepted + 1 of them)
    accepted: int                 # accepted draft tokens
    verify_syncs: int             # host transfers spent verifying
    active: np.ndarray            # (K,) final active mask


def probs_from_logits(logits: jax.Array, temp: float, top_k: int,
                      vocab_size: int) -> jax.Array:
    """Temperature + top-k filtered probabilities over the TRUE vocab."""
    logits = logits[..., :vocab_size].astype(jnp.float32)
    if temp <= 0:
        # Greedy as a limiting case: delta on the argmax.
        return jax.nn.one_hot(jnp.argmax(logits, -1), vocab_size)
    logits = logits / temp
    if top_k and top_k < vocab_size:
        # k-th largest via lax.top_k: O(N log k), not a full O(N log N)
        # sort of the 256k-vocab row on every scoring call.
        kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits >= kth, logits, -jnp.inf)
    return jax.nn.softmax(logits, axis=-1)


def block_randomness(sub: jax.Array, draft_len: int, num_drafts: int,
                     vocab: int):
    """Shared log-uniforms + strategy key stream for one block: the RNG
    contract (DESIGN.md §3.2) every engine path must follow for the
    coupling — and the cross-engine exact-match tests — to hold."""
    k_unif, k_strat = jax.random.split(sub)
    log_u = jnp.log(jax.random.uniform(
        k_unif, (draft_len + 1, num_drafts, vocab),
        minval=np.finfo(np.float32).tiny, maxval=1.0))
    return log_u, jax.random.split(k_strat, draft_len + 1)


@functools.lru_cache(maxsize=None)
def _jitted_buffer_forward(mcfg: ModelConfig):
    """Process-wide jitted buffer forward, one per ModelConfig (frozen,
    hashable).  Engines used to hold per-instance jit closures, so every
    fresh engine re-traced and re-compiled identical forwards — in the
    strategy benchmarks that billed several seconds of XLA compile time
    to whichever strategy happened to run first (the 2x "gls lag" of
    BENCH_specdec.json).  jax.jit's shape-keyed cache on a shared
    callable makes engine construction compile-free after the first."""
    def f(p, t):
        return forward(p, mcfg, {"tokens": t}, remat=False)
    return jax.jit(f)


class SpecDecEngine:
    """Speculative decoding over one target and K (possibly distinct)
    drafters sharing the target's vocabulary."""

    def __init__(self, target: tuple, drafters: Sequence[tuple],
                 cfg: SpecDecConfig):
        self.t_params, self.t_cfg = target
        self.drafters = list(drafters)
        if len(self.drafters) == 1 and cfg.num_drafts > 1:
            self.drafters = self.drafters * cfg.num_drafts
        assert len(self.drafters) == cfg.num_drafts
        self.cfg = cfg
        self.vocab = self.t_cfg.vocab_size
        self._homogeneous = (
            all(d is self.drafters[0] for d in self.drafters)
            and len(set(cfg.temps)) == 1)
        # Serving instrumentation (read by the scheduler / benchmarks).
        self.num_target_forwards = 0
        self.num_draft_forwards = 0
        # Device->host transfers spent materializing draft tokens (one
        # per draft step per block/round; DESIGN.md §7.3 accounting).
        self.num_draft_syncs = 0

    def set_verifier_backend(self, backend: str) -> None:
        """Degradation-ladder rung (scheduler fault recovery, DESIGN.md
        §13): swap the block-verification backend in place.  Token-
        invisible — the backends are exact-equality oracles of one
        another (tests/test_block_verify.py)."""
        self.cfg = dataclasses.replace(self.cfg, verifier_backend=backend)

    # -- durable state (DESIGN.md §14) --------------------------------------
    def export_state(self) -> dict:
        """Snapshot-facing engine facts (the stateless-reference subset
        of ``CachedSpecDecEngine.export_state``): config identity for
        restore compatibility checks plus the one ladder-mutable flag."""
        cfg = self.cfg
        return {
            "strategy": cfg.strategy,
            "num_drafts": int(cfg.num_drafts),
            "draft_len": int(cfg.draft_len),
            "vocab": int(self.vocab),
            "quant": bool(cfg.quant),
            "paged": bool(cfg.paged),
            "page_size": int(cfg.page_size),
            "verifier_backend": cfg.verifier_backend,
            "verify_dequantized": False,
        }

    _RESTORE_COMPAT = ("strategy", "num_drafts", "draft_len", "vocab",
                       "quant", "paged", "page_size")

    def restore_state(self, state: dict) -> None:
        mine = self.export_state()
        for f in self._RESTORE_COMPAT:
            if f in state and state[f] != mine[f]:
                raise ValueError(
                    f"restore onto incompatible engine: {f}="
                    f"{mine[f]!r} but the snapshot was written with "
                    f"{f}={state[f]!r}")
        backend = state.get("verifier_backend")
        if backend and backend != self.cfg.verifier_backend:
            self.set_verifier_backend(backend)

    # -- jitted, shape-stable model calls ---------------------------------
    def _buffer_forward(self, params, mcfg: ModelConfig, tokens: jax.Array):
        return _jitted_buffer_forward(mcfg)(params, tokens)

    # -- shared drafting / scoring core (R requests stacked) ---------------
    def _block_randomness(self, sub: jax.Array):
        return block_randomness(sub, self.cfg.draft_len,
                                self.cfg.num_drafts, self.vocab)

    def _draft_block(self, log_u_all: jax.Array, bufs: np.ndarray,
                     p0s: np.ndarray):
        """Autoregressive draft loop over R stacked requests.

        log_u_all: (R, L+1, K, N) device; bufs: (R, K, T) host buffers
        (mutated in place); p0s: (R,) prefix lengths.  Returns
        (draft_tokens (R, K, L) host, draft_probs (R, K, L, N) device or
        None).  One drafter forward per step covers all R*K rows when the
        drafters are homogeneous; else one per drafter over the R rows.
        """
        cfg = self.cfg
        r_n, k_n, t_n = bufs.shape
        l_n, n = cfg.draft_len, self.vocab
        need_probs = cfg.strategy in RS_STRATEGIES
        d_tokens = np.zeros((r_n, k_n, l_n), np.int32)
        prob_steps = []
        rows = np.arange(k_n)
        for j in range(l_n):
            pos = p0s + j - 1                                   # (R,)
            if self._homogeneous:
                params, mcfg = self.drafters[0]
                logits = self._buffer_forward(
                    params, mcfg, jnp.asarray(bufs.reshape(r_n * k_n, t_n)))
                self.num_draft_forwards += 1
                sel = logits[jnp.arange(r_n * k_n),
                             jnp.asarray(np.repeat(pos, k_n))]
                p_all = probs_from_logits(sel, cfg.temps[0], cfg.top_k, n)
            else:
                cols = []
                for k in range(k_n):
                    params, mcfg = self.drafters[k]
                    logits = self._buffer_forward(
                        params, mcfg, jnp.asarray(bufs[:, k]))
                    self.num_draft_forwards += 1
                    sel = logits[jnp.arange(r_n), jnp.asarray(pos)]
                    cols.append(probs_from_logits(sel, cfg.temps[k],
                                                  cfg.top_k, n))
                p_all = jnp.stack(cols, axis=1).reshape(r_n * k_n, n)
            toks = V.draft_token_from_uniforms(
                log_u_all[:, j].reshape(r_n * k_n, n), p_all)
            tk = np.asarray(toks).reshape(r_n, k_n)  # 1 transfer / step
            self.num_draft_syncs += 1
            d_tokens[:, :, j] = tk
            for r in range(r_n):
                bufs[r, rows, p0s[r] + j] = tk[r]
            if need_probs:
                prob_steps.append(p_all)
        d_probs = None
        if need_probs:
            d_probs = jnp.stack(prob_steps).reshape(
                l_n, r_n, k_n, n).transpose(1, 2, 0, 3)
        return d_tokens, d_probs

    def _score_block(self, bufs: np.ndarray, p0s: np.ndarray) -> jax.Array:
        """ONE target forward over all R*K stacked draft buffers; gathers
        q(. | X^(k)_{1:j}, c) at each request's L+1 scoring positions.
        Returns (R, K, L+1, N)."""
        cfg = self.cfg
        r_n, k_n, t_n = bufs.shape
        l_n = cfg.draft_len
        logits = self._buffer_forward(
            self.t_params, self.t_cfg, jnp.asarray(bufs.reshape(r_n * k_n,
                                                                t_n)))
        self.num_target_forwards += 1
        pos = np.stack([np.arange(p0 - 1, p0 + l_n) for p0 in p0s])
        rowpos = np.repeat(pos, k_n, axis=0)                # (R*K, L+1)
        sel = logits[jnp.arange(r_n * k_n)[:, None], jnp.asarray(rowpos)]
        q = probs_from_logits(sel, cfg.target_temp, cfg.top_k, self.vocab)
        return q.reshape(r_n, k_n, l_n + 1, self.vocab)

    # -- speculative blocks -------------------------------------------------
    def gen_blocks(self, subs: Sequence[jax.Array],
                   prefixes: Sequence[np.ndarray],
                   buf_len: int) -> list:
        """Advance R requests by one speculative block each: one batched
        draft loop, ONE target forward, one fused verification per
        request.  Per-request RNG streams (``subs``) are independent, so
        the result is bit-identical to R sequential ``gen_block`` calls.
        Returns a list of BlockOutcome."""
        cfg = self.cfg
        r_n, k_n = len(prefixes), cfg.num_drafts
        rand = [self._block_randomness(s) for s in subs]
        log_u_all = jnp.stack([lu for lu, _ in rand])    # (R, L+1, K, N)
        p0s = np.asarray([len(p) for p in prefixes])
        bufs = np.zeros((r_n, k_n, buf_len), np.int32)
        for r, pre in enumerate(prefixes):
            bufs[r, :, :len(pre)] = pre
        d_tokens, d_probs = self._draft_block(log_u_all, bufs, p0s)
        q = self._score_block(bufs, p0s)
        outs = []
        # Verification dispatches per request (R jitted calls, R
        # transfers per round).  A vmapped (R, ...) block_verify with one
        # device_get would cut this to a single transfer; it is kept
        # per-request for now so the batched path stays trivially
        # bit-identical to the sequential one.
        for r in range(r_n):
            hb = run_block_verify(
                log_u_all[r], d_tokens[r],
                None if d_probs is None else d_probs[r], q[r], rand[r][1],
                strategy=cfg.strategy, backend=cfg.verifier_backend,
                interpret=cfg.pallas_interpret)
            outs.append(BlockOutcome(new_tokens=hb.new_tokens,
                                     accepted=hb.num_accepted,
                                     verify_syncs=hb.host_syncs,
                                     active=hb.active))
        return outs

    def gen_block(self, key: jax.Array, prefix: np.ndarray,
                  buf_len: int) -> BlockOutcome:
        """Single-request speculative block (the R=1 case of gen_blocks)."""
        return self.gen_blocks([key], [np.asarray(prefix, np.int32)],
                               buf_len)[0]

    def _gen_block(self, key: jax.Array, prefix: np.ndarray, buf_len: int):
        """Back-compat shim for the pre-refactor private API."""
        out = self.gen_block(key, prefix, buf_len)
        return out.new_tokens, out.accepted

    # -- public API ---------------------------------------------------------
    def generate(self, key: jax.Array, prompt: np.ndarray,
                 max_new: Optional[int] = None) -> GenerationStats:
        max_new = max_new or self.cfg.max_new_tokens
        prefix = np.asarray(prompt, np.int32)
        buf_len = len(prefix) + max_new + self.cfg.draft_len + 2
        blocks = 0
        accepted = 0
        syncs = 0
        n0 = len(prefix)
        while len(prefix) - n0 < max_new:
            key, sub = jax.random.split(key)
            out = self.gen_block(sub, prefix, buf_len)
            prefix = np.concatenate(
                [prefix, np.asarray(out.new_tokens, np.int32)])
            blocks += 1
            accepted += out.accepted
            syncs += out.verify_syncs
        return GenerationStats(output=prefix[n0:n0 + max_new], blocks=blocks,
                               accepted_drafts=accepted, host_syncs=syncs)

    def serve(self, key: jax.Array, prompts: Sequence[np.ndarray],
              max_new: Optional[int] = None) -> list:
        """Batched serving: each request advances one speculative block per
        round; model calls batch over live requests x drafts."""
        results = []
        for i, prompt in enumerate(prompts):
            results.append(self.generate(jax.random.fold_in(key, i),
                                         prompt, max_new))
        return results


def autoregressive_reference(key: jax.Array, target: tuple,
                             prompt: np.ndarray, max_new: int,
                             temp: float = 1.0, top_k: int = 50,
                             use_gumbel_trace: bool = True) -> np.ndarray:
    """Plain autoregressive sampling from the target — the distribution
    speculative decoding must preserve.  With ``use_gumbel_trace`` the
    sampler uses the same per-step Gumbel-race construction as GLS with
    K=1 so sequence-level equality (not just distributional) can be
    checked under shared randomness."""
    params, mcfg = target
    prefix = np.asarray(prompt, np.int32)
    buf_len = len(prefix) + max_new + 1
    fwd = jax.jit(lambda p, t: forward(p, mcfg, {"tokens": t}, remat=False))
    buf = np.zeros((1, buf_len), np.int32)
    buf[0, :len(prefix)] = prefix
    out = []
    n = len(prefix)
    for i in range(max_new):
        key, sub = jax.random.split(key)
        logits = fwd(params, jnp.asarray(buf))[0, n - 1 + i]
        probs = probs_from_logits(logits, temp, top_k, mcfg.vocab_size)
        if use_gumbel_trace:
            log_u = jnp.log(jax.random.uniform(
                sub, (mcfg.vocab_size,),
                minval=np.finfo(np.float32).tiny, maxval=1.0))
            tok = int(V.gumbel_race_argmin(log_u, probs))
        else:
            tok = int(jax.random.categorical(
                sub, jnp.log(jnp.maximum(probs, 1e-30))))
        out.append(tok)
        buf[0, n + i] = tok
    return np.asarray(out, np.int32)

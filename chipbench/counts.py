"""Operations and bytes of the served round, computed from shapes.

Model sizes come from a configuration file of ``chipbench/configs``
(Hugging Face key names; see ``model_dims``).  Nothing here imports the
program: the parameter count follows the llama layout the program
serves (untied ``lm_head``, vocabulary padded to a multiple of 256),
and the collective model is the enumeration of the serving
tensor-parallel all-gathers (one heads regather before ``wo``, one
``d_model`` gather after it, one ``d_ff`` gather after gate/up, one
``d_model`` gather after ``w_down`` per layer, and one padded-vocab
logits gather per forward), gathered at 4 bytes an element.

FLOPs count a multiply-add as 2.  ``round_flops`` counts what the
algorithm needs for the requests that advance: the weight matmuls of
every row of a live slot, and attention over each row's real context,
not over the padded buffer.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Dims:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int

    @property
    def padded_vocab(self) -> int:
        return -(-self.vocab // 256) * 256

    def layer_params(self) -> int:
        d, hd = self.d_model, self.head_dim
        attn = d * self.heads * hd * 2 + d * self.kv_heads * hd * 2
        return attn + 3 * d * self.d_ff + 2 * d

    def params(self) -> int:
        """Every parameter the program initialises (norm scales too)."""
        return (self.layers * self.layer_params()
                + 2 * self.padded_vocab * self.d_model + self.d_model)

    def matmul_params(self) -> int:
        """Parameters a token multiplies by: all but the embedding table
        and the norm scales."""
        return (self.layers * (self.layer_params() - 2 * self.d_model)
                + self.d_model * self.padded_vocab)


def model_dims(c: dict) -> Dims:
    heads = int(c["num_attention_heads"])
    return Dims(layers=int(c["num_hidden_layers"]),
                d_model=int(c["hidden_size"]), heads=heads,
                kv_heads=int(c.get("num_key_value_heads", heads)),
                head_dim=int(c.get("head_dim")
                             or c["hidden_size"] // heads),
                d_ff=int(c["intermediate_size"]),
                vocab=int(c["vocab_size"]))


def token_flops(m: Dims, context: float) -> float:
    """One token through the model with ``context`` positions to attend
    (QK^T and PV over every head)."""
    attn = 4.0 * m.layers * m.heads * m.head_dim * context
    return 2.0 * m.matmul_params() + attn


def round_flops(target: Dims, drafter: Dims, k: int, l: int,
                contexts) -> float:
    """Useful FLOPs of one fused round for live slots whose cached
    contexts (positions before the pending token) are ``contexts``.

    Per live slot and lane: the drafter decodes L + 1 single tokens (the
    L-step sweep and the catch-up step) and the target verifies L + 1
    tokens (the pending token and L drafts)."""
    total = 0.0
    for ctx in contexts:
        for j in range(l + 1):
            total += k * (token_flops(drafter, ctx + j + 1)
                          + token_flops(target, ctx + j + 1))
    return total


def race_call(rows: int, k: int, vocab: int) -> dict:
    """The ``gls_row_race`` kernel over (rows, K, N) f32 race inputs:
    it reads log S and log q once and writes (min, argmin) per row.
    About two operations an element (a subtraction and a compare)."""
    elems = rows * k * vocab
    return {"bytes": 2.0 * elems * 4 + rows * k * 8,
            "flops": 2.0 * elems}


def round_race(slots: int, k: int, l: int, vocab: int) -> dict:
    """The one race call of a fused round: every slot's (L+1) steps."""
    return race_call(slots * (l + 1), k, vocab)


def prefill_flops(m: Dims, prompt_len: int) -> float:
    """Causal prefill of ``prompt_len`` tokens into one row: the sum of
    ``token_flops(m, p + 1)`` over positions p < prompt_len."""
    n = prompt_len
    return (2.0 * m.matmul_params() * n
            + 4.0 * m.layers * m.heads * m.head_dim * n * (n + 1) / 2)


def tp_round_collective_bytes(target: Dims, drafter: Dims, slots: int,
                              k: int, l: int, tp: int) -> float:
    """All-gather bytes one device moves in one fused round at ``tp``
    (ring traffic: gathered bytes x (tp - 1) / tp)."""
    if tp <= 1:
        return 0.0
    rows = slots * k
    frac = (tp - 1) / tp

    def fwd(m: Dims, tokens: int) -> float:
        per_layer = rows * tokens * (m.heads * m.head_dim + 2 * m.d_model
                                     + m.d_ff)
        logits = rows * tokens * m.padded_vocab
        return (per_layer * m.layers + logits) * 4 * frac

    return (l + 1) * fwd(drafter, 1) + fwd(target, l + 1)

"""Compile-only guards for the TPU: the main path's kernels and the
fused speculative round, compiled with ``interpret=False`` for a
described (not attached) v5e chip at SmolLM-360M widths (B=8, H=15,
Hkv=5, T=2048, D=64; vocab 49152; mamba2-370m's for the SSD kernel).
Nothing runs; the TPU compiler
refuses here what it would refuse on the chip (illegal block shapes,
tiling, fast-memory budgets), and each test checks that the kernel is
really in the program (``tpu_custom_call``), not its jnp fallback.

The topology is described inside a module fixture, never at import, so
every test worker collects the same tests and only the worker running
this file loads the TPU compiler.  Where no v5e topology can be
described the tests skip.
"""

import functools
import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
B, H, HKV, T, D = 8, 15, 5, 2048, 64
VOCAB = 49_152


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        # Keep the TPU compiler's logs out of the file system, and the
        # persistent cache out of these compiles (an entry written for
        # a described chip cannot be read back without one).
        mp.setenv("TPU_LOG_DIR", "disabled")
        cache_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        try:
            try:
                desc = topologies.get_topology_desc(
                    platform="tpu", topology_name="v5e:2x2")
            except Exception as e:
                pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", cache_on)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_hlo(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def test_decode_attention_compiles(one_chip):
    from repro.kernels.decode_attention.kernel import decode_attention
    hlo = _compiled_hlo(
        functools.partial(decode_attention, interpret=False),
        _spec(one_chip, (B, H, D)), _spec(one_chip, (B, HKV, T, D)),
        _spec(one_chip, (B, HKV, T, D)), _spec(one_chip, (B,), jnp.int32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("masks", [False, True],
                         ids=["plain", "q_offset_kv_len"])
def test_flash_attention_compiles(one_chip, masks):
    from repro.kernels.flash_attention.kernel import flash_attention
    args = [_spec(one_chip, (B, H, 128, D)), _spec(one_chip, (B, HKV, T, D)),
            _spec(one_chip, (B, HKV, T, D))]
    if masks:
        args += [_spec(one_chip, (B,), jnp.int32),
                 _spec(one_chip, (B,), jnp.int32)]
    hlo = _compiled_hlo(functools.partial(flash_attention, interpret=False),
                        *args)
    assert "tpu_custom_call" in hlo


def test_gls_row_race_compiles(one_chip):
    """The fused round's race: S*(L+1) = 40 rows, K = 8 drafts."""
    from repro.kernels.gls_race.kernel import gls_row_race
    hlo = _compiled_hlo(functools.partial(gls_row_race, interpret=False),
                        _spec(one_chip, (40, 8, VOCAB)),
                        _spec(one_chip, (40, 8, VOCAB)))
    assert "tpu_custom_call" in hlo


def test_gls_binned_race_compiles(one_chip):
    """The Wyner-Ziv race: K+1 = 3 sheets over 2^15 atoms, 4 bins."""
    from repro.kernels.gls_race.kernel import gls_binned_race
    n = 2 ** 15
    hlo = _compiled_hlo(
        functools.partial(gls_binned_race, l_max=4, interpret=False),
        _spec(one_chip, (16, 3, n)), _spec(one_chip, (16, 3, n)),
        _spec(one_chip, (16, n), jnp.int32))
    assert "tpu_custom_call" in hlo


def test_qdot_int8_compiles(one_chip, monkeypatch):
    """W8A8 verify matmul at a SmolLM-360M round's rows (8 slots x 8
    drafts x 5 positions) into d_ff: the native s8 x s8 -> s32 dot
    (the branch ``qdot`` takes on a TPU backend)."""
    from repro.serving import quant
    monkeypatch.setattr(quant.jax, "default_backend", lambda: "tpu")
    hlo = _compiled_hlo(
        quant.qdot, _spec(one_chip, (8 * 8 * 5, 960)),
        {"q": _spec(one_chip, (960, 2560), jnp.int8),
         "s": _spec(one_chip, (2560,))})
    assert "s8[" in hlo and "s32[" in hlo


# Arena-shaped copies (``copy``, or an async ``copy-start``/``copy-done``
# pair) in the compiled fused round below before the rollback copied
# only its window: layout changes around the layer scans.  The windowed
# rollback must add none.
ROUND_ARENA_COPIES = 10


def _arena_ops(hlo: str, shapes) -> list:
    """(op, line) of every instruction whose result, or a tuple result's
    first element, has one of ``shapes`` (dims joined by commas)."""
    out = []
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = \(?\w+\[([\d,]*)\]\S* ([\w-]+)\(",
                     line)
        if m and m.group(1) in shapes:
            out.append((m.group(2), line))
    return out


def test_fused_round_compiles(one_chip):
    """The kv_fused round core — drafter sweep, stacked verify, the
    Pallas race verifier, rollback — at SmolLM-360M / SmolLM-135M widths
    cut to 2 layers each, 8 slots x K=8 x L=4, lowered with abstract
    shapes as ``launch/dryrun.py`` lowers the sharded round.  The
    rollback copies only each row's window: no gather with a whole
    arena's result, one in-place dynamic-update-slice per arena leaf,
    and no arena-shaped copy beyond those the whole-row gather's round
    had."""
    from repro.configs import get_config
    from repro.models import init_cache, init_params
    from repro.specdec.engine import SpecDecConfig
    from repro.specdec.engine_cached import build_round_core

    slots, k, buf = 8, 8, 256
    t_cfg = get_config("smollm-360m").replace(dtype="float32", num_layers=2)
    d_cfg = get_config("smollm-135m").replace(dtype="float32", num_layers=2)
    sd = SpecDecConfig(num_drafts=k, draft_len=4, strategy="gls",
                       verifier_backend="pallas", pallas_interpret=False)

    def on_chip(tree):
        return jax.tree.map(lambda a: _spec(one_chip, a.shape, a.dtype), tree)

    def arena(cfg):
        c = jax.eval_shape(lambda: init_cache(cfg, slots * k, buf))
        return on_chip({"k": c["k"], "v": c["v"]})

    key = jax.random.PRNGKey(0)
    args = (on_chip(jax.eval_shape(lambda: init_params(key, t_cfg))),
            on_chip(jax.eval_shape(lambda: init_params(key, d_cfg))),
            arena(t_cfg), arena(d_cfg),
            _spec(one_chip, (slots,), jnp.int32),
            _spec(one_chip, (slots,), jnp.int32),
            _spec(one_chip, (slots,), jnp.bool_),
            _spec(one_chip, (slots, 2), jnp.uint32))
    round_core = build_round_core(sd, t_cfg, d_cfg, t_cfg.vocab_size, slots)
    compiled = jax.jit(round_core, donate_argnums=(2, 3, 4)).lower(
        *args).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    # The chip benchmark finds the race kernel and the top-k sorts by
    # their HLO instruction text, which the round's named scopes leave
    # alone (DESIGN.md §16).
    sys.path.insert(0, ROOT)
    from chipbench.programs import RACE, SORT
    ops = [line.strip().removeprefix("ROOT ") for line in hlo.splitlines()]
    assert any(re.search(RACE, op) for op in ops)
    assert any(re.search(SORT, op) for op in ops)

    arenas = (args[2], args[3])
    shapes = {",".join(map(str, a["k"].shape)) for a in arenas}
    arena_ops = _arena_ops(hlo, shapes)
    assert not [line for op, line in arena_ops if op == "gather"]
    writes = [op for op, line in arena_ops if "/rollback/" in line
              and op not in ("get-tuple-element", "bitcast")]
    assert writes == ["dynamic-update-slice"] * sum(len(a) for a in arenas)
    copies = [line for op, line in arena_ops if op in ("copy", "copy-done")]
    assert len(copies) <= ROUND_ARENA_COPIES, copies


def test_ssd_chunk_compiles(one_chip):
    """Mamba-2 SSD intra-chunk kernel at mamba2-370m widths: chunk 64,
    32 heads of 64, state 128."""
    from repro.kernels.ssd_chunk.kernel import ssd_chunk
    b, nc, q, h, p, n = 2, 4, 64, 32, 64, 128
    hlo = _compiled_hlo(
        functools.partial(ssd_chunk, interpret=False),
        _spec(one_chip, (b, nc, q, h, p)), _spec(one_chip, (b, nc, q, h)),
        _spec(one_chip, (h,)), _spec(one_chip, (b, nc, q, n)),
        _spec(one_chip, (b, nc, q, n)))
    assert "tpu_custom_call" in hlo

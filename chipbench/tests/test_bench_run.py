"""The command end to end on the CPU at a tiny size: it refuses to
measure off the chip, and with the chip look skipped its output check
passes a sound run and fails the controls and planted faults."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from chipbench import run

HERE = os.path.dirname(os.path.abspath(__file__))
TINY_TRAFFIC = os.path.join(HERE, "data", "traffic")
TINY = {"configs": [{"name": "tiny",
                     "file": "chipbench/tests/data/tiny.json"}],
        "workloads": [{"name": "tiny-open", "config": "tiny",
                       "traffic": "tiny-open", "chips": 1}],
        "end_to_end": [{"name": "ttft_p95_ms", "unit": "ms"},
                       {"name": "tpot_p95_ms", "unit": "ms"},
                       {"name": "setup_s", "unit": "s"}],
        "per_layer": []}


def test_refuses_a_cpu(capsys):
    rc = run.main(["--workload", "smollm-chat-open", "--seed", "1",
                   "--seconds", "1"])
    out, err = capsys.readouterr()
    assert rc == 3 and out == ""
    assert "no TPU" in err


def test_fails_without_the_program(tmp_path):
    """A checkout holding only BENCHMARK.json and the benchmark's files
    exits non-zero and prints no result."""
    shutil.copy(os.path.join(run.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(run.ROOT, "chipbench"),
                    tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run([sys.executable, "chipbench/run.py", "--workload",
                        "smollm-chat-open", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=tmp_path, capture_output=True,
                       text=True, timeout=120)
    assert p.returncode != 0 and p.stdout == ""


def _tiny(seed, **kw):
    return run.run("tiny-open", seed, 6.0, False, bench=TINY,
                   require_tpu=False, traffic_dir=TINY_TRAFFIC, **kw)


def test_sound_run_is_correct():
    out = _tiny(2 ** 31 + 17)
    assert out["correct"], out["check"]
    assert out["check"]["tokens_checked"]["value"] > 100
    assert out["check"]["accepted_checked"]["value"] >= 0
    assert set(out["metrics"]) == {"ttft_p95_ms", "tpot_p95_ms", "setup_s"}
    assert out["attempted"] > 0 and out["failed"] == 0
    json.dumps(out)


def test_quant_control_is_not_correct():
    """The program's W8A8 + int8-KV path, the precision below what the
    configuration computes, fails the check."""
    out = _tiny(2 ** 31 + 17, control="quant")
    assert not out["correct"], out["check"]


def test_bf16_control_is_not_correct():
    """The reference computed in bfloat16, put in the program's place
    (the tokens it puts first at each served position), fails the
    check; the served tokens' own reading stays sound."""
    out = run.run("tiny-open", 2 ** 31 + 17, 6.0, False, bench=TINY,
                  require_tpu=False, traffic_dir=TINY_TRAFFIC,
                  control="bf16")
    assert not out["correct"], out["check"]


def test_accepted_draft_fault_is_not_correct(monkeypatch):
    """A verify that keeps each block's first token and then accepts
    draft 0's remaining drafts and the bonus, as if every draft matched,
    fails the check: the tokens after the first are judged too."""
    import jax.numpy as jnp
    from repro.specdec import engine_cached
    verify = engine_cached.block_verify_batched

    def accept_all(log_u, d_tokens, d_probs, q, keys, **kw):
        res = verify(log_u, d_tokens, d_probs, q, keys, **kw)
        l = d_tokens.shape[2]
        tokens = jnp.concatenate([res.tokens[:, :1], d_tokens[:, 0, 1:],
                                  res.tokens[:, l:]], axis=1)
        return res._replace(tokens=tokens,
                            num_accepted=jnp.full_like(res.num_accepted, l),
                            active=jnp.ones_like(res.active))

    monkeypatch.setattr(engine_cached, "block_verify_batched", accept_all)
    out = _tiny(11)
    assert not out["correct"]
    assert out["check"]["accepted_checked"]["value"] > 100
    assert out["check"]["mismatch_share"]["value"] > 50.0


def test_altered_token_is_not_correct(monkeypatch):
    """A token altered where the fused round produces it (every emitted
    token moved to the next vocabulary id) fails the check."""
    from repro.specdec.engine_cached import CachedSpecDecEngine
    build = CachedSpecDecEngine._build_fused_round

    def broken(self):
        fn = build(self)

        def round_(*args):
            t_kv, d_kv, pos, packed = fn(*args)
            packed = dict(packed, tokens=(packed["tokens"] + 1) % self.vocab)
            return t_kv, d_kv, pos, packed
        return round_

    monkeypatch.setattr(CachedSpecDecEngine, "_build_fused_round", broken)
    out = _tiny(5)
    assert not out["correct"]
    assert out["check"]["mismatch_share"]["value"] > 50.0


def test_queue_wait_reads_requests_due_before_the_trace():
    """The traced run's queue wait leaves out requests due after the
    profiler started (its stop holds the serving loop), and reads
    nothing where no request was due before it."""
    import types

    def rec(due, admit):
        return types.SimpleNamespace(
            due=due, request=types.SimpleNamespace(t_admit=admit))
    records = [rec(1.0, 1.2), rec(2.0, 2.1), rec(3.0, 3.4), rec(4.0, None),
               rec(12.0, 20.0)]
    read = run.reader("queue_wait_p50_ms")
    assert read({"records": records, "trace_started": 10.0}) == \
        pytest.approx(200.0)
    assert read({"records": records, "trace_started": 0.5}) is None
    assert read({"summary": {}}) is None


def test_traced_run_reads_its_per_layer_metrics():
    """``--trace 1`` profiles a sub-window and hands the readers the
    window's records and the profiler's start."""
    bench = dict(TINY, per_layer=[{"name": "queue_wait_p50_ms",
                                   "unit": "ms"}])
    out = run.run("tiny-open", 2 ** 31 + 99, 6.0, True, bench=bench,
                  require_tpu=False, traffic_dir=TINY_TRAFFIC)
    assert out["correct"], out["check"]
    assert set(out["metrics"]) == {"queue_wait_p50_ms"}
    assert out["metrics"]["queue_wait_p50_ms"]["value"] >= 0.0
    assert out["device"]["window_s"] > 0

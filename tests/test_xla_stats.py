"""XLA memory/compile ledger (ISSUE 17): memory-analysis field
extraction, observed-jit AOT capture with per-signature compile caching,
manifest SUM semantics, the drift gate (`diff_manifests` /
`analyze programs`), and the KV cache's flag-off program-set parity.
"""

import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distributed_tensorflow_tpu.models.gpt import GPTLM
from distributed_tensorflow_tpu.observability.xla_stats import (
    ProgramLedger, diff_manifests, memory_fields)
from distributed_tensorflow_tpu.serving import (
    ContinuousBatcher, Request, SlotKVCache, VirtualClock)


class _FakeMem:
    def __init__(self, arg=0, out=0, temp=0, code=0, alias=0):
        self.argument_size_in_bytes = arg
        self.output_size_in_bytes = out
        self.temp_size_in_bytes = temp
        self.generated_code_size_in_bytes = code
        self.alias_size_in_bytes = alias


class _FakeCompiled:
    def __init__(self, mem):
        self._mem = mem

    def memory_analysis(self):
        if isinstance(self._mem, Exception):
            raise self._mem
        return self._mem


# ------------------------------------------------------------- extraction


def test_memory_fields_decomposition():
    f = memory_fields(_FakeCompiled(_FakeMem(arg=100, out=40, temp=25,
                                             code=7, alias=30)))
    assert f["argument_bytes"] == 100 and f["temp_bytes"] == 25
    assert f["generated_code_bytes"] == 7
    # peak = arg + out + temp − alias
    assert f["peak_bytes_est"] == 100 + 40 + 25 - 30


def test_memory_fields_absent_backend():
    """memory_analysis raising or returning None must degrade to zeros —
    observability never takes the serving path down."""
    for compiled in (_FakeCompiled(RuntimeError("no analysis")),
                     _FakeCompiled(None)):
        f = memory_fields(compiled)
        assert f["peak_bytes_est"] == 0
        assert all(v == 0 for v in f.values())
    # alias larger than the rest clamps at zero, never negative
    f = memory_fields(_FakeCompiled(_FakeMem(arg=1, alias=100)))
    assert f["peak_bytes_est"] == 0


# ------------------------------------------------------------ observed jit


def test_observed_jit_caches_per_signature():
    """One AOT compile per abstract signature; results equal plain
    jax.jit; a second shape is a second compile of the SAME named
    program (compiles aggregates, bytes keep the max)."""
    ledger = ProgramLedger()
    fn = lambda x: x * 2.0 + 1.0
    observed = ledger.jit(fn, name="double")
    x = jnp.arange(8, dtype=jnp.float32)
    np.testing.assert_array_equal(observed(x), jax.jit(fn)(x))
    observed(x + 1.0)            # same signature — no new compile
    rec = ledger.programs()["double"]
    assert rec["compiles"] == 1
    assert rec["compile_s"] > 0.0
    y = jnp.arange(16, dtype=jnp.float32)
    np.testing.assert_array_equal(observed(y), jax.jit(fn)(y))
    assert ledger.programs()["double"]["compiles"] == 2
    m = ledger.manifest()
    assert m["program_count"] == 1 and m["schema_version"] == 1
    assert m["compile_total_s"] == pytest.approx(
        ledger.programs()["double"]["compile_s"])


def test_manifest_sum_semantics():
    """Per-run peak estimate SUMS per-program peaks (every program's
    buffers resident in a serving process); same-name recompiles keep
    the max bytes and total the compile seconds."""
    ledger = ProgramLedger()
    ledger.capture("a", _FakeCompiled(_FakeMem(arg=10, out=5, temp=2)),
                   compile_s=0.5)
    ledger.capture("b", _FakeCompiled(_FakeMem(arg=100, out=50)),
                   compile_s=0.25)
    ledger.capture("a", _FakeCompiled(_FakeMem(arg=8, out=5, temp=40)),
                   compile_s=0.5)
    m = ledger.manifest()
    a, b = m["programs"]["a"], m["programs"]["b"]
    assert a["compiles"] == 2 and a["compile_s"] == pytest.approx(1.0)
    # per-field max across same-name captures
    assert a["argument_bytes"] == 10 and a["temp_bytes"] == 40
    assert m["peak_hbm_bytes_est"] == \
        a["peak_bytes_est"] + b["peak_bytes_est"]
    assert m["compile_total_s"] == pytest.approx(1.25)
    assert json.loads(json.dumps(m)) == m    # JSON-ready


# --------------------------------------------------------------- drift gate


def _manifest(progs):
    return {"schema_version": 1, "programs": progs,
            "program_count": len(progs)}


def test_diff_manifests_gate():
    base = _manifest({"decode": {"temp_bytes": 1000},
                      "prefill": {"temp_bytes": 500}})
    # identical → no findings
    assert diff_manifests(base, base) == []
    # growth under threshold → no findings
    cur = _manifest({"decode": {"temp_bytes": 1050},
                     "prefill": {"temp_bytes": 500}})
    assert diff_manifests(cur, base, temp_threshold=0.10) == []
    # growth past threshold → fail
    cur = _manifest({"decode": {"temp_bytes": 1200},
                     "prefill": {"temp_bytes": 500}})
    [f] = diff_manifests(cur, base, temp_threshold=0.10)
    assert f["severity"] == "fail" and f["kind"] == "temp_bytes_grew"
    assert f["relative"] == pytest.approx(0.2)
    # a NEW program → fail; zero-baseline temp growth → fail (absolute)
    cur = _manifest({"decode": {"temp_bytes": 1000},
                     "prefill": {"temp_bytes": 500},
                     "paged_copy": {"temp_bytes": 1}})
    kinds = {f["kind"] for f in diff_manifests(cur, base)}
    assert kinds == {"program_added"}
    # removal is informational only — shrinking never fails
    cur = _manifest({"decode": {"temp_bytes": 1000}})
    [f] = diff_manifests(cur, base)
    assert f["severity"] == "info" and f["kind"] == "program_removed"


def test_analyze_programs_cli_gate(tmp_path, capsys):
    """The CLI form of the gate: exit 0 against itself, exit 1 when the
    baseline is missing a program the new manifest compiled."""
    from distributed_tensorflow_tpu.observability import analyze
    cur = _manifest({"decode": {"temp_bytes": 10},
                     "prefill": {"temp_bytes": 5}})
    base = _manifest({"decode": {"temp_bytes": 10}})
    p_cur = tmp_path / "cur.json"
    p_base = tmp_path / "base.json"
    p_cur.write_text(json.dumps(cur))
    p_base.write_text(json.dumps(base))
    assert analyze.main(["programs", str(p_cur)]) == 0
    assert json.loads(capsys.readouterr().out)["programs"] == \
        cur["programs"]
    assert analyze.main(["programs", str(p_cur),
                         "--against", str(p_cur)]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["failed"] == 0 and out["findings"] == []
    assert analyze.main(["programs", str(p_cur),
                         "--against", str(p_base)]) == 1
    out = json.loads(capsys.readouterr().out)
    assert out["failed"] == 1
    assert out["findings"][0]["kind"] == "program_added"
    assert out["program_count"] == {"base": 1, "new": 2}


# ------------------------------------------------------ kv cache coupling


def tiny_gpt():
    return GPTLM(vocab_size=64, hidden=32, layers=1, heads=2, ffn=64,
                 max_len=48, dropout_rate=0.0)


def test_kv_cache_ledger_observes_decode(tmp_path):
    """A ledgered SlotKVCache records its compiled program family with
    nonzero compile seconds AND produces tokens identical to the
    unledgered cache — observation changes nothing that runs."""
    model = tiny_gpt()
    x = jnp.asarray(np.random.default_rng(0).integers(0, 64, (2, 8)),
                    jnp.int32)
    params = model.init(jax.random.key(0), x, train=False)["params"]
    rng = np.random.default_rng(3)
    reqs = lambda: [Request(rid=i,
                            prompt=rng.integers(0, 64, 6).astype(np.int32),
                            max_new_tokens=6, arrival_s=float(i))
                    for i in range(3)]
    rng = np.random.default_rng(3)
    plain_reqs = reqs()
    rng = np.random.default_rng(3)
    led_reqs = reqs()
    kv_plain = SlotKVCache(model, params, slots=2)
    plain = ContinuousBatcher(kv_plain, clock=VirtualClock()).run(plain_reqs)
    ledger = ProgramLedger()
    kv_led = SlotKVCache(model, params, slots=2, ledger=ledger)
    led = ContinuousBatcher(kv_led, clock=VirtualClock()).run(led_reqs)
    for a, b in zip(plain["results"], led["results"]):
        assert a.tokens == b.tokens, (a.rid, a.tokens, b.tokens)
    m = ledger.manifest()
    assert m["programs"], "ledger observed no programs"
    assert m["compile_total_s"] > 0.0
    # the observed names are the cache's own program family, namespaced
    # under the kv_ component prefix
    assert all(name.startswith("kv_") for name in m["programs"])
    assert "kv_decode_step" in m["programs"], sorted(m["programs"])
    assert any(name.startswith("kv_prefill_batched_l")
               for name in m["programs"])
    # flag-off parity at the program level: identical inventories
    assert set(kv_plain.compiled_programs()) == \
        set(kv_led.compiled_programs())

"""Roofline efficiency ledger (ISSUE 19): the analytic GPT cost model's
exact-pinned FLOPs/bytes figures (grad-accum invariance, prefill chunk
telescoping, int8/paged KV byte accounting, speculative verify widths),
the device peak table's honesty contract (unknown kind → None, never an
invented peak), the MFU/MBU wiring through trainer fit results, the
continuous batcher, the replica fleet and the run report (all flag-off
key-set parity pinned), the ProgramLedger's cost_analysis columns, and
the `analyze roofline` / `analyze diff` read side.

Part A runs without jax (the cost model is stdlib-only by contract);
parts B/C exercise the ledger fakes and the live serving/training paths
on the container's fake 8-device CPU mesh.
"""

import json
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distributed_tensorflow_tpu.models.gpt import GPTLM
from distributed_tensorflow_tpu.observability import analyze
from distributed_tensorflow_tpu.observability.report import (
    build_run_report, serve_section)
from distributed_tensorflow_tpu.observability.roofline import (
    PEAK_TABLE_REVISION, DevicePeaks, GPTCostModel, Roofline,
    arithmetic_intensity, attainable_fraction, classify_bound,
    device_peaks, flops_crosscheck, program_attribution, ridge_point)
from distributed_tensorflow_tpu.observability.xla_stats import (
    ProgramLedger, cost_fields, diff_manifests)
from distributed_tensorflow_tpu.serving import (
    ContinuousBatcher, ReplicaSet, Request, SlotKVCache, VirtualClock,
    build_replica_kvs)


# Tiny config every Part A pin is hand-computed against:
#   proj flops/token = 2·h·(h + kv_h + kv_h + h) + 2·h·h   [qkvo]
#                    = 2·4·(4+4+4+4) + ffn path 2·2·4·8 = 128 + 128 = 256
#   lm_head          = 2·h·V = 2·4·16 = 128
TINY = dict(vocab=16, hidden=4, layers=1, heads=2, ffn=8, max_len=32)


def _cost(**over):
    return GPTCostModel(**{**TINY, **over})


def tiny_gpt(**kw):
    kw.setdefault("vocab_size", 64)
    kw.setdefault("hidden", 32)
    kw.setdefault("layers", 2)
    kw.setdefault("heads", 2)
    kw.setdefault("ffn", 64)
    kw.setdefault("max_len", 32)
    kw.setdefault("dropout_rate", 0.0)
    return GPTLM(**kw)


@pytest.fixture(scope="module")
def model_params():
    model = tiny_gpt()
    x = jnp.asarray(np.random.default_rng(0).integers(0, 64, (2, 8)),
                    jnp.int32)
    params = model.init(jax.random.key(0), x, train=False)["params"]
    return model, params


def _requests(n=4, seed=3, max_new=6, spread=0.5):
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=rng.integers(0, 64, 4 + i % 3).astype(np.int32),
                    max_new_tokens=max_new, arrival_s=float(i) * spread)
            for i in range(n)]


# ====================================================================
# Part A — the analytic cost model, exact pins (no jax involved)
# ====================================================================


def test_flops_exact_pins():
    """Hand-computed FLOPs for the tiny config: these are the numbers
    every MFU claim divides by, so they are pinned EXACTLY — any change
    to the accounting is a deliberate, visible diff here."""
    c = _cost()
    assert c._proj_flops_per_token == 256
    assert c.lm_head_flops == 128
    # fwd(L=8): proj 256 + attn 2·2h·(L/2 causal) = 2·8·4 + lm_head 128
    #         = 256 + 64 + 128 = 448
    assert c.fwd_flops_per_token(8) == 448
    assert c.train_flops_per_token(8) == 3 * 448
    # one optimizer step, batch 2 × seq 8: 16 tokens × 1344
    assert c.train_step_flops(2, 8) == 21504
    # decode at context L=5: proj 256 + attn 4h·L (no causal halving at
    # width 1) + lm_head = 256 + 80 + 128 = 464
    assert c.decode_flops_per_token(5) == 464
    # verify width 3 from base 5 = decode(5)+decode(6)+decode(7)
    assert c.verify_flops(5, 3) == 464 + 480 + 496 == 1440
    # prefill chunk n=4 from empty: 4·proj + 4h·(4·0 + 4·5/2) + NO lm
    # head (charged once per finished prefill, not per chunk)
    assert c.prefill_chunk_flops(4, 0) == 1184


def test_param_count_and_bytes():
    c = _cost()
    # embed 16·4 (tied) + pos 32·4 + per-layer qkvo 4·16 + mlp 2·32 = 128
    # + ln/bias-free accounting per the model = 320 params → 1280 f32 B
    assert c.param_count() == 320
    assert c.param_bytes() == 1280
    assert _cost(param_bytes_override=999).param_bytes() == 999


def test_grad_accum_invariance():
    """K microbatches that sum to the same token count cost the same
    model FLOPs — grad accumulation rearranges work, it does not add
    model math (remat is never credited either: BASELINE.md)."""
    c = _cost()
    assert c.train_step_flops(8, 8, grad_accum=1) \
        == c.train_step_flops(8, 8, grad_accum=4)
    with pytest.raises(ValueError, match="grad_accum"):
        c.train_step_flops(8, 8, grad_accum=0)


def test_prefill_chunks_telescope():
    """Chunked prefill sums EXACTLY to the monolithic figure, whatever
    the chunking — the scheduler credits per chunk, and the total must
    not depend on --serve-prefill-chunk."""
    c = _cost()
    whole = c.prefill_chunk_flops(13, 0)
    for size in (1, 3, 5, 13):
        total, start = 0.0, 0
        while start < 13:
            n = min(size, 13 - start)
            total += c.prefill_chunk_flops(n, start)
            start += n
        assert total == whole, size
    assert c.prefill_chunk_flops(0, 4) == 0.0
    assert c.prefill_chunk_flops(-2, 4) == 0.0


def test_kv_bytes_layout_pins():
    """Must-read KV bytes under every storage layout, pinned: f32 is
    2 (k,v) · kv_hidden · 4 B = 32 B/pos; int8 is payload 8 + one f32
    scale per (pos, kv_head) · 2 tensors = 24 B/pos; paged rounds the
    read up to whole blocks (the block-granular gather)."""
    assert _cost().kv_read_bytes(5) == 160                       # 32·5
    assert _cost(kv_dtype="int8").kv_read_bytes(5) == 120        # 24·5
    assert _cost(kv_layout="paged", paged_block=4).kv_read_bytes(5) \
        == 256                                                   # 32·8
    # monolithic credits exactly L — the max_len scan the compiled
    # program actually does is the inefficiency MBU exposes, not credit
    assert _cost().kv_read_bytes(32) == 32 * 32


def test_decode_step_bytes_pin():
    """One batched decode step reads the params ONCE plus each live
    slot's context KV: 1280 + 32·4 + 32·8 = 1664."""
    c = _cost()
    assert c.decode_step_bytes([4, 8]) == 1664
    # bytes do NOT scale with verify width — the whole point of
    # speculative decoding's bandwidth win
    assert c.decode_step_bytes([4]) == c.decode_step_bytes([4])


def test_moe_and_gqa_variants():
    """MoE: active params price FLOPs (top-1 routing), storage prices
    bytes.  GQA: shrunken kv projections shrink BOTH proj FLOPs and
    KV bytes/position."""
    moe = _cost(moe_experts=2)
    assert moe.param_count(active_only=True) == 328
    assert moe.param_count(active_only=False) == 392
    # decode at empty context isolates proj+lm_head: 272 + 128
    assert moe.decode_flops_per_token(0) == 400
    gqa = _cost(kv_heads=1)
    assert gqa._proj_flops_per_token == 224
    assert gqa._kv_bytes_per_position == 16


def test_peak_table_entries_and_revision():
    p = device_peaks("TPU v5e")
    assert p is not None and p.revision == PEAK_TABLE_REVISION == 1
    assert p.flops_per_s["bf16"] == 197e12
    assert p.flops_per_s["f32"] == 197e12 / 2
    assert p.flops_per_s["int8"] == 2 * 197e12
    assert p.hbm_bytes_per_s == 819e9
    # "TPU v5 lite" is what the chip tool's v5e reports (PR 21 chip run)
    assert device_peaks("TPU v5 lite").flops_per_s["bf16"] == 197e12
    # no catch-all: an unlisted v5 string is unknown, not priced as a v5p
    assert device_peaks("TPU v5") is None
    assert device_peaks("TPU v4").flops_per_s["bf16"] == 275e12


def test_unknown_device_is_none_never_invented():
    assert device_peaks("cpu") is None
    assert device_peaks("") is None
    assert device_peaks(None) is None
    rf = Roofline.for_device("cpu", n_devices=8)
    assert rf.peaks is None
    assert rf.mfu(1e12) is None and rf.mbu(1e9) is None
    d = rf.describe()
    assert d["known_device"] is False
    assert d["peak_flops_per_sec"] is None
    assert d["peak_table_revision"] == PEAK_TABLE_REVISION


def test_mfu_normalizes_over_devices():
    rf = Roofline.for_device("TPU v5e", n_devices=2)
    assert rf.mfu(1e13) == pytest.approx(1e13 / (2 * 197e12))
    assert rf.mfu(1e13) == pytest.approx(0.025380710659898477)
    assert rf.mfu(None) is None
    assert rf.mbu(819e9) == pytest.approx(0.5)  # 2 chips' worth of HBM


def test_roofline_geometry_helpers():
    p = device_peaks("TPU v5e")
    ridge = ridge_point(p, "bf16")
    assert ridge == pytest.approx(197e12 / 819e9)
    assert arithmetic_intensity(100.0, 50.0) == 2.0
    assert arithmetic_intensity(100.0, 0) is None
    assert arithmetic_intensity(None, 50.0) is None
    assert classify_bound(ridge * 2, p, "bf16") == "compute"
    assert classify_bound(ridge / 2, p, "bf16") == "bandwidth"
    assert classify_bound(2.0, None, "bf16") is None
    assert attainable_fraction(ridge, p, "bf16") == pytest.approx(1.0)
    assert attainable_fraction(ridge / 4, p, "bf16") == pytest.approx(0.25)
    assert ridge_point(None, "bf16") is None


def test_from_model_requires_causal_lm():
    class NotALM:
        pass

    assert GPTCostModel.from_model(NotALM()) is None
    assert GPTCostModel.from_model(None) is None
    c = GPTCostModel.from_model(tiny_gpt())
    assert c is not None
    assert (c.vocab, c.hidden, c.layers) == (64, 32, 2)


def test_flops_crosscheck_ratio():
    assert flops_crosscheck(100.0, 300.0) == pytest.approx(3.0)
    assert flops_crosscheck(None, 300.0) is None
    assert flops_crosscheck(100.0, None) is None
    assert flops_crosscheck(0.0, 300.0) is None


# ====================================================================
# Part B — ledger cost columns, attribution, the analyze read side
# ====================================================================


class _FakeMem:
    def __init__(self, arg=0, out=0, temp=0, code=0, alias=0):
        self.argument_size_in_bytes = arg
        self.output_size_in_bytes = out
        self.temp_size_in_bytes = temp
        self.generated_code_size_in_bytes = code
        self.alias_size_in_bytes = alias


class _FakeCompiled:
    def __init__(self, mem, cost=None):
        self._mem = mem
        self._cost = cost

    def memory_analysis(self):
        if isinstance(self._mem, Exception):
            raise self._mem
        return self._mem

    def cost_analysis(self):
        if isinstance(self._cost, Exception):
            raise self._cost
        return self._cost


def test_cost_fields_extraction():
    """XLA spells the bytes key with a SPACE ('bytes accessed'); absent
    or zero data is None — 'no data', never 'zero work'."""
    f = cost_fields(_FakeCompiled(None, [{"flops": 100.0,
                                          "bytes accessed": 50.0}]))
    assert f == {"flops": 100.0, "bytes_accessed": 50.0}
    assert cost_fields(_FakeCompiled(None, RuntimeError("no backend"))) \
        == {"flops": None, "bytes_accessed": None}
    assert cost_fields(_FakeCompiled(None, [{"flops": 0.0}])) \
        == {"flops": None, "bytes_accessed": None}


def test_ledger_manifest_carries_cost_columns():
    ledger = ProgramLedger()
    ledger.capture("step", _FakeCompiled(
        _FakeMem(arg=10), [{"flops": 100.0, "bytes accessed": 50.0}]),
        compile_s=0.1)
    ledger.capture("blind", _FakeCompiled(_FakeMem(arg=5)), compile_s=0.1)
    progs = ledger.manifest()["programs"]
    assert progs["step"]["flops"] == 100.0
    assert progs["step"]["bytes_accessed"] == 50.0
    assert progs["blind"]["flops"] is None
    assert progs["blind"]["bytes_accessed"] is None


def test_program_attribution_rows():
    progs = {"step": {"flops": 100.0, "bytes_accessed": 50.0},
             "blind": {"flops": None, "bytes_accessed": None}}
    rows = program_attribution(progs, peaks=device_peaks("TPU v5e"))
    by = {r["program"]: r for r in rows}
    assert by["step"]["arithmetic_intensity"] == 2.0
    # 2 flops/byte is far under the v5e ridge (~240) → bandwidth-bound,
    # attainable ≈ 2/ridge of peak
    assert by["step"]["bound"] == "bandwidth"
    assert by["step"]["attainable_frac_of_peak"] == pytest.approx(
        2.0 / (197e12 / 819e9), abs=1e-4)
    assert by["blind"]["arithmetic_intensity"] is None
    assert by["blind"]["bound"] is None
    # no peaks: intensity still renders, bound/%-of-peak honestly None
    rows = program_attribution(progs, peaks=None)
    by = {r["program"]: r for r in rows}
    assert by["step"]["arithmetic_intensity"] == 2.0
    assert by["step"]["bound"] is None


def test_diff_manifests_flops_growth_warns_not_fails():
    """+50% flops on an existing program is a WARN (roofline drift worth
    seeing), not a FAIL — only program_added/temp-bytes growth gate."""
    base = {"programs": {"step": {"flops": 100.0, "bytes_accessed": 50.0,
                                  "temp_bytes": 10}}}
    cur = {"programs": {"step": {"flops": 150.0, "bytes_accessed": 50.0,
                                 "temp_bytes": 10}}}
    findings = diff_manifests(cur, base)
    kinds = {f["kind"]: f["severity"] for f in findings}
    assert kinds.get("flops_grew") == "warn"
    assert [f for f in findings if f["severity"] == "fail"] == []
    # None columns on either side never warn (no data ≠ zero work)
    blind = {"programs": {"step": {"flops": None, "bytes_accessed": None,
                                   "temp_bytes": 10}}}
    assert all(f["kind"] != "flops_grew"
               for f in diff_manifests(cur, blind))


def test_analyze_programs_gate_flops_vs_added(tmp_path):
    base = tmp_path / "base.json"
    base.write_text(json.dumps(
        {"programs": {"step": {"flops": 100.0, "bytes_accessed": 50.0,
                               "temp_bytes": 10, "peak_bytes_est": 10}}}))
    grown = tmp_path / "grown.json"
    grown.write_text(json.dumps(
        {"programs": {"step": {"flops": 150.0, "bytes_accessed": 50.0,
                               "temp_bytes": 10, "peak_bytes_est": 10}}}))
    added = tmp_path / "added.json"
    added.write_text(json.dumps(
        {"programs": {"step": {"flops": 100.0, "bytes_accessed": 50.0,
                               "temp_bytes": 10, "peak_bytes_est": 10},
                      "extra": {"flops": 1.0, "bytes_accessed": 1.0,
                                "temp_bytes": 1, "peak_bytes_est": 1}}}))
    # flops growth alone: warn → exit 0
    assert analyze.main(["programs", str(grown),
                         "--against", str(base)]) == 0
    # a new program: fail → exit 1
    assert analyze.main(["programs", str(added),
                         "--against", str(base)]) == 1


def test_analyze_diff_gates_utilizations(tmp_path):
    """train_mfu / serve_decode_mbu / serve_prefill_mfu are
    higher-is-better gated metrics: a regression past threshold exits 1,
    an improvement exits 0."""
    for key in ("train_mfu", "serve_decode_mbu", "serve_prefill_mfu"):
        assert dict(analyze._DIFF_METRICS)[key] == "higher"
    good = tmp_path / "good.json"
    bad = tmp_path / "bad.json"
    good.write_text(json.dumps({"train_mfu": 0.40,
                                "serve_decode_mbu": 0.60}))
    bad.write_text(json.dumps({"train_mfu": 0.20,
                               "serve_decode_mbu": 0.60}))
    assert analyze.main(["diff", str(good), str(good)]) == 0
    assert analyze.main(["diff", str(good), str(bad)]) == 1   # regressed
    assert analyze.main(["diff", str(bad), str(good)]) == 0   # improved


def test_value_direction_learns_utilization_units():
    assert analyze._value_direction({"metric": "train_mfu"}) == "higher"
    assert analyze._value_direction({"metric": "decode_mbu"}) == "higher"
    assert analyze._value_direction(
        {"metric": "slot_utilization"}) == "higher"
    # existing directions unharmed
    assert analyze._value_direction({"metric": "itl_p50_ms"}) == "lower"
    assert analyze._value_direction(
        {"metric": "grad_bytes", "unit": "bytes"}) == "lower"


def test_analyze_roofline_subcommand(tmp_path, capsys):
    report = {
        "train_mfu": 0.1234,
        "roofline": {"device": {"device_kind": "TPU v5e",
                                "dtype": "bf16",
                                "peak_table_revision": 1}},
        "xla": {"programs": {"step": {"flops": 100.0,
                                      "bytes_accessed": 50.0}}},
    }
    p = tmp_path / "report.json"
    p.write_text(json.dumps(report))
    assert analyze.main(["roofline", str(p)]) == 0
    text = capsys.readouterr().out
    assert "TPU v5e" in text and "train_mfu=0.1234" in text
    assert analyze.main(["roofline", str(p), "--json"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["known_device"] is True
    assert out["peak_table_revision"] == 1
    assert out["programs"][0]["bound"] == "bandwidth"
    # unknown device degrades honestly: intensity renders, bound None
    report["roofline"]["device"]["device_kind"] = "cpu"
    p.write_text(json.dumps(report))
    assert analyze.main(["roofline", str(p), "--json"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["known_device"] is False
    assert out["programs"][0]["arithmetic_intensity"] == 2.0
    assert out["programs"][0]["bound"] is None
    # a report with no manifest still renders the headline
    p.write_text(json.dumps({"train_mfu": 0.2}))
    assert analyze.main(["roofline", str(p), "--device",
                         "TPU v4"]) == 0


# ====================================================================
# Part C — live wiring: batcher, spec decode, fleet, trainer, report
# ====================================================================


def test_batcher_flag_off_parity(model_params):
    """Without --roofline the summary key set is byte-identical to
    round 18: no serve_prefill_mfu / serve_decode_mbu / roofline keys."""
    model, params = model_params
    s = ContinuousBatcher(SlotKVCache(model, params, slots=2),
                          clock=VirtualClock()).run(_requests())
    assert "serve_prefill_mfu" not in s
    assert "serve_decode_mbu" not in s
    assert "roofline" not in s


def test_batcher_single_request_exact_accounting(model_params):
    """One request, no chunking: the batcher's tallies are EXACTLY the
    cost model's figures — prefill = whole-prompt chunk + one lm_head,
    decode = one token per round at contexts P..P+M-2 (the first token
    falls out of the prefill program)."""
    model, params = model_params
    kv = SlotKVCache(model, params, slots=1)
    rf = Roofline.for_kv(kv, "TPU v5e", 1)
    cost = rf.cost
    assert cost is not None
    s = ContinuousBatcher(kv, clock=VirtualClock(), roofline=rf).run(
        [Request(rid=0, prompt=np.arange(5, dtype=np.int32),
                 max_new_tokens=4, arrival_s=0.0)])
    sec = s["roofline"]
    assert sec["prefill_model_flops"] == pytest.approx(
        cost.prefill_chunk_flops(5, 0) + cost.lm_head_flops)
    want_flops = sum(cost.decode_flops_per_token(L) for L in (5, 6, 7))
    want_bytes = sum(cost.decode_step_bytes([L]) for L in (5, 6, 7))
    assert sec["decode_model_flops"] == pytest.approx(want_flops)
    assert sec["decode_must_read_bytes"] == pytest.approx(want_bytes)
    # device phase clocks are real seconds even under VirtualClock, so a
    # known device yields real utilizations
    assert sec["prefill_s"] > 0 and sec["decode_s"] > 0
    assert 0 < s["serve_prefill_mfu"] < 1
    assert 0 < s["serve_decode_mbu"] < 1
    assert sec["device"]["device_kind"] == "TPU v5e"
    assert sec["device"]["peak_table_revision"] == PEAK_TABLE_REVISION


def test_batcher_chunked_prefill_same_totals(model_params):
    """Chunked prefill must credit the SAME total prefill flops as
    monolithic admission (the telescoping pin, now end-to-end)."""
    model, params = model_params
    reqs = [Request(rid=0, prompt=np.arange(13, dtype=np.int32),
                    max_new_tokens=3, arrival_s=0.0)]
    runs = []
    for chunk in (0, 4):
        kv = SlotKVCache(model, params, slots=1)
        rf = Roofline.for_kv(kv, "TPU v5e", 1)
        s = ContinuousBatcher(kv, clock=VirtualClock(),
                              prefill_chunk=chunk, roofline=rf).run(
            [Request(rid=r.rid, prompt=r.prompt,
                     max_new_tokens=r.max_new_tokens,
                     arrival_s=r.arrival_s) for r in reqs])
        runs.append(s["roofline"]["prefill_model_flops"])
    assert runs[0] == pytest.approx(runs[1])


def test_batcher_unknown_device_honest_none(model_params):
    """On an unknown device kind the tallies still accumulate (they are
    analytic) but every utilization is None — never a fabricated peak."""
    model, params = model_params
    kv = SlotKVCache(model, params, slots=2)
    rf = Roofline.for_kv(kv, "cpu", 1)
    s = ContinuousBatcher(kv, clock=VirtualClock(), roofline=rf).run(
        _requests())
    assert s["serve_prefill_mfu"] is None
    assert s["serve_decode_mbu"] is None
    sec = s["roofline"]
    assert sec["prefill_model_flops"] > 0
    assert sec["decode_must_read_bytes"] > 0
    assert sec["device"]["known_device"] is False


def test_spec_decode_same_flops_fewer_bytes(model_params):
    """Same-model draft (every proposal accepted): the verify tiles sum
    to EXACTLY the sequential decode flops — verify at base L, width w
    covers contexts L..L+w-1 — while must-read bytes strictly shrink
    (one param+KV read per ROUND, and there are fewer rounds).  That
    byte asymmetry IS speculative decoding's bandwidth win, and the
    draft's own work is deliberately uncounted (target-model MFU/MBU)."""
    model, params = model_params
    reqs = _requests(n=2, max_new=6, spread=0.0)

    def run(draft):
        kv = SlotKVCache(model, params, slots=2)
        rf = Roofline.for_kv(kv, "TPU v5e", 1)
        kw = dict(clock=VirtualClock(), roofline=rf)
        if draft:
            kw.update(draft_kv=SlotKVCache(model, params, slots=2),
                      draft_k=3)
        return ContinuousBatcher(kv, **kw).run(
            [Request(rid=r.rid, prompt=r.prompt,
                     max_new_tokens=r.max_new_tokens,
                     arrival_s=r.arrival_s) for r in reqs])

    base, spec = run(False), run(True)
    assert {r.rid: r.tokens for r in base["results"]} \
        == {r.rid: r.tokens for r in spec["results"]}
    assert spec["roofline"]["decode_model_flops"] == pytest.approx(
        base["roofline"]["decode_model_flops"])
    assert spec["roofline"]["decode_must_read_bytes"] \
        < base["roofline"]["decode_must_read_bytes"]


# round 20 fast-lane repair: fleet composition variant — the
# single-replica exact accounting pins stay fast
@pytest.mark.slow
def test_fleet_aggregation_and_parity(model_params):
    """ReplicaSet folds window tallies into fleet totals + a per-replica
    breakdown; without --roofline the fleet summary keeps round-18 keys."""
    model, params = model_params
    rs = ReplicaSet(build_replica_kvs(model, params, 2, 2),
                    clock=VirtualClock())
    s0 = rs.run(_requests())
    rs.close()
    assert "serve_prefill_mfu" not in s0 and "roofline" not in s0

    rs = ReplicaSet(build_replica_kvs(model, params, 2, 2),
                    clock=VirtualClock(),
                    roofline=Roofline.for_kv(
                        SlotKVCache(model, params, 1), "TPU v5e", 1))
    s = rs.run(_requests())
    rs.close()
    sec = s["roofline"]
    per = sec["per_replica"]
    assert len(per) == 2
    for key in ("prefill_model_flops", "decode_model_flops",
                "decode_must_read_bytes"):
        assert sec[key] == pytest.approx(sum(r[key] for r in per))
    assert sec["decode_model_flops"] > 0
    assert isinstance(s["serve_prefill_mfu"], float)
    assert isinstance(s["serve_decode_mbu"], float)


def test_trainer_fit_roofline_wiring():
    """The trainer's --roofline plumbing, pinned host-side (this
    container's jax build lacks shard_map, so fit itself cannot run
    here — the CI roofline smoke covers the live path): fit accepts the
    kwarg defaulting None, and Roofline.for_model builds the exact cost
    model the fit-result figure divides by."""
    import inspect

    from distributed_tensorflow_tpu.engines import Trainer

    sig = inspect.signature(Trainer.fit)
    assert "roofline" in sig.parameters
    assert sig.parameters["roofline"].default is None

    model = tiny_gpt(layers=1)
    rf = Roofline.for_model(model, "TPU v5e", n_devices=8)
    assert rf.n_devices == 8 and rf.cost is not None
    # the figure fit reports as train_model_flops_per_step for a
    # batch-64 × seq-16 LM step, and its MFU over 8 v5e chips
    step = rf.cost.train_step_flops(64, 16)
    assert step == 64 * 16 * rf.cost.train_flops_per_token(16)
    achieved = step / 0.010                      # a 10 ms step
    # the compute dtype follows the MODEL (f32 here), so MFU divides by
    # the f32 peak — half the bf16 figure, not a flattering bf16 claim
    assert rf.dtype == "f32"
    assert rf.mfu(achieved) == pytest.approx(
        achieved / (8 * 197e12 / 2))
    assert rf.revision == PEAK_TABLE_REVISION
    # unknown device: the cost model still prices the step, MFU is None
    rf_cpu = Roofline.for_model(model, "cpu", n_devices=8)
    assert rf_cpu.cost.train_step_flops(64, 16) == step
    assert rf_cpu.mfu(achieved) is None


def test_run_report_roofline_section(model_params):
    """build_run_report: flag-off parity; flag-on adds the device/train/
    serve/programs section and hoists train_mfu for analyze diff."""
    model, params = model_params
    fit = {"elapsed": 2.0, "steps": 10, "examples": 640,
           "train_model_flops_per_step": 1000.0,
           "train_achieved_flops_per_sec": 5000.0,
           "train_mfu": 0.25}
    off = build_run_report(dict(fit))
    assert "roofline" not in off and "train_mfu" not in off

    kv = SlotKVCache(model, params, slots=2)
    rf = Roofline.for_kv(kv, "TPU v5e", 1)
    serve = ContinuousBatcher(kv, clock=VirtualClock(),
                              roofline=rf).run(_requests())
    ledger = ProgramLedger()
    ledger.capture("step", _FakeCompiled(
        _FakeMem(arg=10), [{"flops": 3000.0, "bytes accessed": 100.0}]))
    rep = build_run_report(dict(fit),
                           serve=serve_section(serve, len(serve["results"])),
                           ledger=ledger, roofline=rf)
    sec = rep["roofline"]
    assert sec["device"]["device_kind"] == "TPU v5e"
    assert sec["train"]["mfu"] == 0.25
    # XLA counted 3000 over 10 steps vs analytic 1000/step… crosscheck
    # is per-run xla_total/analytic_total — just pin it is a float
    assert isinstance(sec["train"]["xla_flops_crosscheck"],
                      (float, type(None)))
    assert sec["serve"]["decode_model_flops"] > 0
    assert sec["programs"][0]["program"] == "step"
    assert rep["train_mfu"] == 0.25
    # the serve section surfaced the gated keys for analyze diff
    assert "serve_decode_mbu" in rep["serve"]


def test_experiment_config_flag_default():
    from distributed_tensorflow_tpu.utils.harness import ExperimentConfig

    assert ExperimentConfig().roofline is False

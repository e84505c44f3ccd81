"""The selective-scan / multi-query-attention configuration's files: the tiny
cell through the new driver, its comparison against control and planted
faults, the configuration against the catalog row key by key, the cost
functions against the weights that are made, the new readers."""

from __future__ import annotations

import json

import pytest

from helpers import ROOT, load, run_tiny

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL, NAME = "serve-jamba2-longctx", "ai21-jamba2-3b"
CONFIG = json.loads((ROOT / f"benchmarks/configs/{NAME}.json").read_text())
# the catalog's row (/opt/skills/guides/model-configs/architectures.jsonl,
# ``AI21-Jamba2-3B``: its ``config``)
PUBLISHED = {
    "attn_layer_offset": 7, "attn_layer_period": 14, "expert_layer_offset": 1,
    "expert_layer_period": 2, "hidden_act": "silu", "hidden_size": 2560,
    "intermediate_size": 8192, "mamba_conv_bias": True, "mamba_d_conv": 4,
    "mamba_d_state": 16, "mamba_dt_rank": 160, "mamba_expand": 2,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "model_type": "jamba", "num_attention_heads": 20, "num_experts": 1,
    "num_experts_per_tok": 1, "num_hidden_layers": 28,
    "num_key_value_heads": 1, "num_logits_to_keep": 1, "rms_norm_eps": 1e-06,
    "sliding_window": None, "tie_word_embeddings": True,
    "use_mamba_kernels": True, "vocab_size": 65536}


def cells_of(metric: dict) -> list[str]:
    return metric.get("workloads", [w["name"] for w in BENCH["workloads"]])


def cell_file(name: str = CELL) -> dict:
    return json.loads((ROOT / "benchmarks/workloads" / f"{name}.json")
                      .read_text())


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_the_configuration_holds_the_published_key(key):
    assert key in CONFIG and CONFIG[key] == PUBLISHED[key]
    assert type(CONFIG[key]) is type(PUBLISHED[key])


def test_nothing_is_reduced_and_what_is_assumed_is_listed():
    entry = next(c for c in BENCH["configs"] if c["name"] == NAME)
    assert entry["reduced"] == [] and CONFIG["_published"] == {}
    assert entry["source"] == CONFIG["_source"] == (
        "https://huggingface.co/ai21labs/AI21-Jamba2-3B/blob/main/"
        "config.json")
    assert set(CONFIG) - set(PUBLISHED) == {
        "_name", "_source", "_published", "_reduced_why", "deployment",
        "assumed"}
    assert {"layer_order", "head_dim", "experts", "initializer_range",
            "conv_std", "conv_bias_std", "dt_range", "weights", "held_dtype",
            "state_dtype", "positions"} <= set(CONFIG["assumed"])
    assert CONFIG["assumed"]["dt_range"] == [0.001, 0.1]
    assert CONFIG["assumed"]["initializer_range"] == 0.02


def test_the_arithmetic_of_the_whole_model():
    from benchmarks.lib import jamba_costs as costs
    from benchmarks.lib import jamba_weights as weights

    assert weights.param_count(CONFIG) == 3_029_337_472
    assert weights.layer_params(CONFIG, "M") == 104_161_472
    assert weights.layer_params(CONFIG, "A") == 76_682_240
    pattern = weights.sizes(CONFIG)["pattern"]
    assert [i for i, k in enumerate(pattern) if k == "A"] == [7, 21]
    assert len(pattern) == 28 and pattern.count("M") == 26
    # the mixer alone, as the issue counts it
    mixer = weights.layer_params(CONFIG, "M") - 3 * 2560 * 8192 - 2 * 2560
    assert mixer == 41_241_792
    assert costs.matrix_params(CONFIG, "M") == mixer - (
        4 * 5120 + 5120 + 192 + 5120 + 5120 * 16 + 5120) + 3 * 2560 * 8192
    cell = cell_file()
    slots, max_len = cell["job"]["slots"], cell["job"]["max_len"]
    state = 26 * (5120 * 16 * 4 + 3 * 5120 * 2)
    assert state == 9_318_400 and slots * state == 298_188_800
    assert slots * max_len * 1024 == 1_073_741_824      # 1,024 bytes a token


def test_the_tree_mapping_relabels_copies_nothing_and_checks_its_keys():
    import jax
    import jax.numpy as jnp

    from benchmarks.drivers import jamba_tree
    from benchmarks.lib import jamba_weights
    from distributed_tensorflow_tpu.models import create_model

    config = load("tiny-jamba")
    weights = jamba_weights.make(config, 3)
    tree = jamba_tree.to_flax(weights)
    assert {id(leaf) for leaf in jax.tree.leaves(tree)} \
        == {id(leaf) for leaf in jax.tree.leaves(weights)}
    model = create_model("jamba", dtype="bfloat16", param_dtype="bfloat16",
                         **jamba_tree.model_kwargs(config, 128))
    want = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((1, 4), jnp.int32)))["params"]
    assert jax.tree.map(lambda t: (t.shape, str(t.dtype)), tree) \
        == jax.tree.map(lambda t: (t.shape, str(t.dtype)), want)
    w0 = weights["layers"][0]
    dt = jax.nn.softplus(w0["dt_bias"])
    assert bool(jnp.all((dt > 0.0099) & (dt < 0.301)))
    assert bool(jnp.all(jnp.exp(w0["a_log"]).round() == jnp.arange(1, 17)))
    assert bool(jnp.all(w0["d"] == 1.0))
    for key, other in (("model_type", "mamba2"), ("mamba_conv_bias", False),
                       ("mamba_proj_bias", True), ("hidden_act", "gelu"),
                       ("tie_word_embeddings", False),
                       ("sliding_window", 4096), ("num_experts", 16),
                       ("num_experts_per_tok", 2)):
        with pytest.raises(ValueError, match=key):
            jamba_tree.model_kwargs({**config, key: other}, 128)
    with pytest.raises(ValueError, match="positions"):
        jamba_tree.model_kwargs(config, 256)
    # the real configuration's fields, from its file
    real = jamba_tree.model_kwargs(CONFIG, 32768)
    assert real == {
        "vocab_size": 65536, "hidden": 2560, "layers": 28, "attn_period": 14,
        "attn_offset": 7, "ssm_state": 16, "ssm_conv": 4, "ssm_expand": 2,
        "ssm_dt_rank": 160, "heads": 20, "kv_heads": 1, "head_dim": 128,
        "ffn": 8192, "eps": 1e-6, "max_len": 32768}
    assert create_model("jamba", **real).selective_scan_layers == 26


def order_rules(mix: dict, seconds: float, vocab: int, max_len: int):
    """PERF.md section 4's rule (the three longest decodes all due in the
    first half of the window), section 7 (9)'s (none of the ten longest
    decodes among the last tenth of the arrivals) and, for section 7 (n)
    (the work still owed when the arrivals end: here a prompt of tens of
    thousands of tokens is seconds of prefill), none of the four longest
    prompts among the last tenth either; as a function of an
    ``order_seed``."""
    from benchmarks.lib import traffic

    def meets(order_seed: int) -> bool:
        t = traffic.request_trace(1, {**mix, "order_seed": order_seed},
                                  seconds, vocab, max_len)
        by_decode = sorted(t, key=lambda r: -r["max_new_tokens"])
        by_prompt = sorted(t, key=lambda r: -len(r["prompt"]))
        last_tenth = {r["rid"] for r in t[-max(1, len(t) // 10):]}
        return all(r["arrival_s"] < seconds / 2 for r in by_decode[:3]) \
            and not any(r["rid"] in last_tenth
                        for r in by_decode[:10] + by_prompt[:4])
    return meets


def test_the_cell_asks_for_the_issues_traffic():
    from benchmarks.lib import traffic

    seconds = float(BENCH["run_seconds"])
    cell = cell_file()
    mix = cell["traffic"]
    assert mix["prompt_tokens"] == {"median": 8192, "sigma": 0.7, "lo": 2048,
                                    "hi": 31744}
    assert mix["new_tokens"] == {"median": 256, "sigma": 0.6, "lo": 64,
                                 "hi": 1024}
    assert (cell["job"]["slots"], cell["job"]["max_len"]) == (32, 32768)
    assert {cell["job"][k] for k in ("dtype", "param_dtype", "kv_dtype")} \
        == {"bfloat16"}
    entry = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["chips"]) == (NAME, 1)
    trace = traffic.request_trace(1, mix, seconds, 65536, 32768)
    buckets = {max(8, 1 << (len(r["prompt"]) - 1).bit_length())
               for r in trace}
    assert buckets == {2048, 4096, 8192, 16384, 32768}      # each is warmed
    assert {max(8, 1 << (n - 1).bit_length())
            for n in cell["job"]["warm_prompt_lens"]} == buckets
    assert max(r["max_new_tokens"] for r in trace) <= cell["check"]["pad_new"]
    assert max(len(r["prompt"]) + r["max_new_tokens"] for r in trace) <= 32768
    # the reference's longest pass is the table's length, not more
    pad = cell["check"]["pad_to"]
    assert max(pad * -(-(len(r["prompt"]) - 1 + cell["check"]["pad_new"])
                       // pad) for r in trace) == 32768
    meets = order_rules(mix, seconds, 65536, 32768)
    chosen = mix["order_seed"]
    assert meets(chosen)
    assert not any(meets(s) for s in range(chosen))


def test_the_cell_reports_the_shares_of_the_peak_and_the_new_metrics():
    mine = {m["name"] for m in BENCH["per_layer"] if CELL in cells_of(m)}
    new = {"kernel.selective_scan_roofline", "model.selective_scan_share",
           "kvcache.jamba_decode_step_mbu",
           "kvcache.jamba_state_share_of_round_bytes"}
    assert new | {"model.serve_mfu", "device.idle_share.serve",
                  "scheduler.queue_wait_p90_ms",
                  "scheduler.queue_wait_in_prefill_share",
                  "scheduler.batch_occupancy_p50",
                  "kvcache.prefill_ms_per_tok", "kvcache.prefill_pad_share",
                  "kvcache.prefill_device_share",
                  "kvcache.step_dispatch_ms_p50"} <= mine
    for m in BENCH["per_layer"]:
        if m["name"] in new:
            assert m["workloads"] == [CELL]
    # another model's byte counts; no expert layer
    assert not {"kvcache.hybrid_decode_step_mbu", "kvcache.decode_step_mbu",
                "kvcache.state_share_of_round_bytes",
                "model.expert_ffn_share", "moe.experts_touched_p50"} & mine
    reports = {m["name"] for m in BENCH["end_to_end"] if CELL in cells_of(m)}
    assert reports == {"serve_tok_s", "ttft_p90_ms", "setup_s"}
    # the benchmark this PR found, and nothing of it edited: 7 cells, 5
    # configurations and 30 per-layer metrics stand before the new ones
    assert [w["name"] for w in BENCH["workloads"]].index(CELL) == 7
    assert [c["name"] for c in BENCH["configs"]].index(NAME) == 5
    assert [m["name"] for m in BENCH["per_layer"]][30:] == [
        "kernel.selective_scan_roofline", "model.selective_scan_share",
        "kvcache.jamba_decode_step_mbu",
        "kvcache.jamba_state_share_of_round_bytes"]


def test_costs_count_every_parameter_once_and_the_kernels_bytes():
    from benchmarks.lib import jamba_costs as costs
    from benchmarks.lib import jamba_weights as weights

    matrices = 26 * costs.matrix_params(CONFIG, "M") \
        + 2 * costs.matrix_params(CONFIG, "A")
    small = 26 * (4 * 5120 + 5120 + 192 + 5120 + 5120 * 16 + 5120) \
        + 28 * 2 * 2560 + 2560
    assert matrices + small + 65536 * 2560 == weights.param_count(CONFIG)
    # one prompt token alone, one generated token: no decode step is fed
    one = costs.serve_flops(CONFIG, 1, 1)
    want = 2.0 * matrices + 2 * 2.0 * 2 * 2560 + 2.0 * 2560 * 65536
    assert one == pytest.approx(want)
    # a second generated token: one more token through the layers, two
    # keys behind it in each attention layer, the head again
    two = costs.serve_flops(CONFIG, 1, 2) - one
    assert two == pytest.approx(want + 2 * 2.0 * 2 * 2560)
    # a round of 20 streams moves every matrix and the tied embedding once,
    # the float32 vectors of the recurrence, each slot's state twice and
    # its rows once
    parts = costs.decode_round_bytes(CONFIG, 20, 9000.0, 9_318_400, 1024)
    assert parts["weights"] == 2 * (matrices + 65536 * 2560) \
        + 4 * 26 * 5120 * 18
    assert parts["state"] == 2 * 20 * 9_318_400
    assert parts["rows"] == 20 * 9000.0 * 1024
    # the kernel as called at the 32,768 bucket: no product; u, dt and y in
    # float32, B and C, A and D, a state in and out
    call = costs.selective_scan_call(1, 32768, 5120, 16)
    assert call["flops"] == 0
    assert call["bytes"] == 4 * (3 * 32768 * 5120 + 2 * 32768 * 16
                                 + 5120 * 16 + 5120 + 2 * 5120 * 16)


def run_jamba(**kw):
    return run_tiny("tiny-serve-jamba", CELL, **kw)


def test_the_tiny_cell_runs_through_the_new_driver():
    result = run_jamba()
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 4
    want = {m["name"] for m in BENCH["end_to_end"] if CELL in cells_of(m)}
    assert set(result["metrics"]) == want
    assert list(result["checks"]) == ["token_logit_gap", "requests_failed",
                                      "compiles_in_window"]


def test_the_sample_holds_the_longest_and_one_after_a_longer_occupant():
    import jax

    from benchmarks.drivers import serve_jamba
    from benchmarks.lib import traffic
    from helpers import tiny

    _, cell, config = tiny("tiny-serve-jamba", CELL)
    run = serve_jamba.Run(cell, config, seed=9, seconds=2.0,
                          devices=jax.devices()[:1], note=lambda m: None)
    run.build()
    obs = run.serve(traffic.request_trace(9, run.mix, 2.0, run.vocab,
                                          run.max_len))
    assert len(run.slots_held) == obs["attempted"] > run.kv.slots
    assert obs["state_bytes_per_slot"] == 3 * (128 * 16 * 4 + 3 * 128 * 4)
    assert obs["cache_bytes_per_token"] == 2 * 16 * 4
    # three state-space layers: every bucket position and every prompt
    # token of the window went through the kernel three times
    prompts = sum(len(req["prompt"]) for req, _ in run.finished)
    assert obs["ssm_scan_tokens"] == 3 * prompts
    assert obs["ssm_scan_positions"] > obs["ssm_scan_tokens"]
    assert obs["ssm_scan_positions"] % 24 == 0
    sample = run.sample()
    assert len(sample) == cell["check"]["sample_requests"]
    assert run.sampled_after_longer >= 1
    total = lambda f: len(f[0]["prompt"]) + len(f[1])
    assert sample[0][0]["rid"] == max(run.finished, key=total)[0]["rid"]
    # the second was admitted into a slot whose last occupant was longer
    held = dict(run.slots_held)
    mine = sample[1][0]["rid"]
    before = [rid for rid, slot in run.slots_held[:[
        r for r, _ in run.slots_held].index(mine)] if slot == held[mine]]
    sizes = {f[0]["rid"]: total(f) for f in run.finished}
    assert before and sizes[before[-1]] > sizes[mine]


class _Trace:
    """A reduced trace's face to the readers: the device's operations."""

    def __init__(self, events):
        from benchmarks.lib import xplane

        self.ops = {"/device:TPU:0": [xplane.Event(*e) for e in events]}
        self.t0, self.t1 = 0.0, 1e9
        self.busy_s = xplane.busy_ns(self.ops["/device:TPU:0"], 0, 1e9) / 1e9

    def pattern_busy_seconds(self, pattern):
        from benchmarks.lib import xplane

        return xplane.Reduced.pattern_busy_seconds(self, pattern)


def test_the_new_readers_read_the_windows_records_and_the_kernels_events():
    """After a window of the tiny cell: a share of the memory roofline (a
    count against a stand-in peak here, not a device metric) and the
    state's share of the round's bytes; the kernel's share and roofline off
    events named as the v5e's compiler names the call; nothing where there
    is nothing."""
    from benchmarks import run as runmod
    from benchmarks.lib import jamba_costs
    from helpers import cpu_peaks, tiny

    result = run_jamba(seed=11)
    assert result["correct"]
    bench, cell, config = tiny("tiny-serve-jamba", CELL)
    ctx = {"config": config, "cell": cell, "chips": 1, "peaks": cpu_peaks(),
           "trace": None}
    obs = {"decode_context_mean": 30.0, "cache_bytes_per_token": 128,
           "state_bytes_per_slot": 29184}
    spans = ("kvcache.jamba_decode_step_mbu",
             "kvcache.jamba_state_share_of_round_bytes")
    entries = [m for m in bench["per_layer"] if m["name"] in spans]
    got = runmod.evaluate(entries, obs, ctx)
    assert got[spans[0]]["value"] > 0
    assert 0 < got[spans[1]]["value"] < 100
    # no observation (an older driver), or another model's configuration
    assert runmod.evaluate(entries, {}, ctx) == {}
    other = {**ctx, "config": load("tiny-hybrid-ssm")}
    assert runmod.evaluate(entries, obs, other) == {}

    kernel = ("kernel.selective_scan_roofline", "model.selective_scan_share")
    entries = [m for m in bench["per_layer"] if m["name"] in kernel]
    # without a trace the device metrics are left out, never 0
    assert runmod.evaluate(entries, obs, ctx) == {}
    call = ('%selective_scan.{n} = (f32[1,{length},40,128]{{3,2,1,0:T(8,128)}}, '
            'f32[1,16,40,128]{{3,2,1,0:T(8,128)S(1)}}) custom-call(%reshape.11, '
            '%reshape.12), custom_call_target="tpu_custom_call"')
    least = {n: jamba_costs.selective_scan_call(1, n, 5120, 16)["bytes"]
             / cpu_peaks()["hbm_bytes_per_s"] for n in (4096, 32768)}
    events = [(call.format(n=3, length=4096), 0.0, 4 * least[4096] * 1e9),
              (call.format(n=7, length=32768), 2e8, 4 * least[32768] * 1e9),
              ("%fusion.9 = bf16[32768,2560] fusion(%p)", 5e8, 4e8)]
    real = {**ctx, "config": CONFIG, "trace": _Trace(events)}
    got = runmod.evaluate(entries, obs, real)
    assert got[kernel[0]]["value"] == pytest.approx(25.0)
    assert got[kernel[1]]["value"] == pytest.approx(
        100.0 * 4 * (least[4096] + least[32768])
        / (4 * (least[4096] + least[32768]) + 0.4))
    # a trace of a program without the kernel: nothing, not 0
    bare = {**real, "trace": _Trace(events[2:])}
    assert runmod.evaluate(entries, obs, bare) == {}


def test_the_kernels_call_is_named_as_the_pattern_expects():
    """The ``pallas_call``'s name is what the v5e's compiler names the
    custom call by (``tests/test_tpu_compile.py`` holds the compiled text
    to the same pattern); here: the name is in the lowered program."""
    import jax
    import jax.numpy as jnp

    from distributed_tensorflow_tpu.ops import selective_scan as ss

    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    text = jax.jit(ss.selective_scan).lower(
        f32(1, 16, 128), f32(1, 16, 128), f32(128, 16), f32(1, 16, 16),
        f32(1, 16, 16), f32(128)).as_text(debug_info=True)
    assert "selective_scan" in text


VARIANTS = [{"mode": "fp8"}] + [{"fault": f} for f in (
    "state_kept", "pads_advance", "tail_at_bucket_end", "scalar_decay",
    "no_inner_norms", "no_d_skip", "no_conv_bias", "no_dt_bias",
    "gate_before_scan", "attn_off_by_one", "no_ssm_ffn")]


@pytest.fixture(scope="module")
def served():
    import jax

    from benchmarks.drivers import serve_jamba
    from benchmarks.lib import traffic
    from helpers import tiny

    _, cell, config = tiny("tiny-serve-jamba", CELL)
    run = serve_jamba.Run(cell, config, seed=5, seconds=1.0,
                          devices=jax.devices()[:1], note=lambda m: None)
    run.build()
    run.serve(traffic.request_trace(5, run.mix, 1.0, run.vocab, run.max_len))
    return run, run.sample(), cell["limits"]["token_logit_gap"]


def test_every_planted_fault_is_among_the_variants():
    from benchmarks.lib import jamba_reference

    assert [v["fault"] for v in VARIANTS[1:]] == list(jamba_reference.FAULTS)


@pytest.mark.parametrize("variant", VARIANTS,
                         ids=lambda v: next(iter(v.values())))
def test_the_control_and_the_planted_faults_fail_the_limit(served, variant):
    """The served tokens of one window, judged by the reference as it is
    (inside the limit) and by the float8 control or a planted fault (the
    token that variant puts first lies below the reference's best by more
    than the limit)."""
    run, sample, limit = served
    assert run.gaps(sample)["token_logit_gap"] <= limit
    assert run.gaps(sample, **variant)["token_logit_gap"] > limit


def test_a_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    from distributed_tensorflow_tpu.serving.kv_cache import SlotKVCache

    real = SlotKVCache.advance

    def altered(self, *a, **k):
        out = real(self, *a, **k).copy()
        out[0] = (out[0] + 1) % 500
        return out

    monkeypatch.setattr(SlotKVCache, "advance", altered)
    result = run_jamba()
    assert not result["correct"], result["checks"]


def test_a_state_that_is_not_reset_is_not_correct(monkeypatch):
    """The timed path broken underneath: a prefill whose kernel starts from
    a state that is not zero (what a slot's last occupant would have left)
    comes out not correct."""
    import numpy as np

    from distributed_tensorflow_tpu.models import jamba

    real = jamba.selective_scan

    def kept(u, dt, a, b, c, skip, initial_state=None):
        stale = np.full((u.shape[0], u.shape[-1], a.shape[1]), 50.0,
                        np.float32)
        return real(u, dt, a, b, c, skip, stale)

    monkeypatch.setattr(jamba, "selective_scan", kept)
    result = run_jamba()
    assert not result["correct"], result["checks"]


def test_the_calibration_reads_program_control_and_faults(monkeypatch,
                                                          capsys, tmp_path):
    """``calibrate_mla_moe.py`` (it names no model: the cell's driver gives
    ``gaps``) end to end on the tiny cell, the look for a chip taken out: a
    line a seed for the program, then the control and the named faults."""
    import jax

    from benchmarks import calibrate, calibrate_mla_moe, run as runmod
    from helpers import cpu_peaks, tiny

    monkeypatch.setattr(runmod, "load_cell",
                        lambda name: tiny("tiny-serve-jamba", name))
    monkeypatch.setattr(runmod, "require_devices",
                        lambda chips: (jax.devices()[:chips], cpu_peaks()))
    monkeypatch.setattr(calibrate, "OUT", tmp_path)
    assert calibrate_mla_moe.main(
        ["--workload", CELL, "--seeds", "5,8", "--controls", "1",
         "--fault-seeds", "1", "--faults", "state_kept,scalar_decay",
         "--seconds", "1.0"]) == 0
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    assert [(l["seed"], l["who"]) for l in lines] == [
        (5, "program"), (5, "control_fp8"), (5, "fault_state_kept"),
        (5, "fault_scalar_decay"), (8, "program")]
    assert lines[0]["token_logit_gap"] < 0.001 < min(
        l["token_logit_gap"] for l in lines[1:4])

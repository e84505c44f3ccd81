"""The hybrid state-space / attention / latent-expert configuration's
files: the tiny cell through the new driver, its comparison against control
and planted faults, the configuration against the catalog row, the cost
functions against the weights that are made, the new readers; and the
second cell of ``gpt2-large``, which is data alone."""

from __future__ import annotations

import json

import pytest

from helpers import ROOT, load, run_tiny

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL, GEN = "serve-nemotron3s-chat", "serve-gpt2l-gen"
NAME = "nemotron-3-super-120b-a12b-11l-ep4"
CONFIG = json.loads((ROOT / f"benchmarks/configs/{NAME}.json").read_text())
PATTERN = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
           "EMEMEMEMEM*EMEMEMEM*EMEMEMEME")


def cells_of(metric: dict) -> list[str]:
    return metric.get("workloads", [w["name"] for w in BENCH["workloads"]])


def cell_file(name: str) -> dict:
    return json.loads((ROOT / "benchmarks/workloads" / f"{name}.json")
                      .read_text())


def test_the_configuration_holds_the_published_keys():
    """Every key of the catalog row under its own name; depth (with the
    pattern), experts held and vocabulary differ and are listed with the
    published values beside them; no width is among what was cut."""
    published = {
        "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
        "expand": 2, "head_dim": 128, "hidden_size": 4096,
        "hybrid_override_pattern": PATTERN, "intermediate_size": 2688,
        "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64,
        "mamba_hidden_act": "silu", "mamba_num_heads": 128,
        "mamba_proj_bias": False, "max_position_embeddings": 262144,
        "mlp_bias": False, "mlp_hidden_act": "relu2",
        "model_type": "nemotron_h", "moe_intermediate_size": 2688,
        "moe_latent_size": 1024, "moe_shared_expert_intermediate_size": 5376,
        "moe_shared_expert_overlap": False,
        "mtp_hybrid_override_pattern": "*E", "n_group": 1, "n_groups": 8,
        "n_routed_experts": 512, "n_shared_experts": 1, "norm_eps": 1e-05,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 22, "num_hidden_layers": 88,
        "num_key_value_heads": 2, "num_logits_to_keep": 1,
        "num_nextn_predict_layers": 1, "partial_rotary_factor": 1,
        "rescale_prenorm_residual": True, "residual_in_fp32": False,
        "rope_theta": 10000, "routed_scaling_factor": 5,
        "sliding_window": None, "ssm_state_size": 128,
        "tie_word_embeddings": False, "time_step_floor": 0.0001,
        "time_step_max": 0.1, "time_step_min": 0.001, "topk_group": 1,
        "use_bias": False, "use_conv_bias": True, "use_mamba_kernels": True,
        "vocab_size": 131072}
    assert len(PATTERN) == 88
    entry = next(c for c in BENCH["configs"] if c["name"] == NAME)
    differs = {k for k, v in published.items() if CONFIG[k] != v}
    assert differs == set(entry["reduced"]) == {
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
        "vocab_size"}
    assert CONFIG["_published"] == {k: published[k] for k in differs}
    assert set(CONFIG["_reduced_why"]) == differs
    assert CONFIG["hybrid_override_pattern"] == PATTERN[27:38] \
        == "MEMEMEMEM*E"
    assert (CONFIG["num_hidden_layers"], CONFIG["n_routed_experts"],
            CONFIG["experts_held"], CONFIG["vocab_size"]) == (
                11, 128, [0, 128], 32768)
    # the period's ratio is the published one: 40 : 40 : 8
    counts = [CONFIG["hybrid_override_pattern"].count(k) for k in "ME*"]
    assert counts == [5, 5, 1] == [PATTERN.count(k) // 8 for k in "ME*"]
    assert entry["source"] == CONFIG["_source"]
    assert "four chips share each layer" in CONFIG["deployment"]
    assert {"positions", "latent_projections", "mtp", "state_dtype",
            "weights", "max_len", "parameters"} <= set(CONFIG["assumed"])


def test_the_arithmetic_of_the_cut():
    from benchmarks.lib import hybrid_ssm_costs as costs
    from benchmarks.lib import hybrid_ssm_weights as weights

    assert weights.param_count(CONFIG) == 4_648_163_712
    assert weights.layer_params(CONFIG, "M") == 109_640_064
    assert weights.layer_params(CONFIG, "*") == 35_655_680
    assert weights.layer_params(CONFIG, "E") == 759_173_632
    assert costs.expert_params(CONFIG) == 5_505_024     # the catalog's count
    assert costs.outside_experts(CONFIG, "E") == 54_525_952
    whole = {**CONFIG, **CONFIG["_published"]}
    assert 120.6e9 < weights.param_count(whole) < 120.7e9
    cell = cell_file(CELL)
    state = 5 * (128 * 64 * 128 * 4 + 3 * 10240 * 2)
    assert state == 21_278_720
    assert cell["job"]["slots"] * state == 2_723_676_160
    assert cell["job"]["slots"] * cell["job"]["max_len"] * 1024 \
        == 536_870_912


def test_the_tree_mapping_relabels_copies_nothing_and_checks_its_keys():
    import jax
    import jax.numpy as jnp

    from benchmarks.drivers import hybrid_ssm_tree
    from benchmarks.lib import hybrid_ssm_weights
    from distributed_tensorflow_tpu.models import create_model

    config = load("tiny-hybrid-ssm")
    weights = hybrid_ssm_weights.make(config, 3)
    tree = hybrid_ssm_tree.to_flax(weights)
    assert {id(leaf) for leaf in jax.tree.leaves(tree)} \
        == {id(leaf) for leaf in jax.tree.leaves(weights)}
    model = create_model("hybrid_ssm", dtype="bfloat16",
                         param_dtype="bfloat16",
                         **hybrid_ssm_tree.model_kwargs(config, 128))
    assert model.experts_held == (0, 8) and model.num_experts == 16
    want = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((1, 4), jnp.int32)))["params"]
    assert jax.tree.map(lambda t: (t.shape, str(t.dtype)), tree) \
        == jax.tree.map(lambda t: (t.shape, str(t.dtype)), want)
    dt = jax.nn.softplus(weights["layers"][0]["dt_bias"])
    assert bool(jnp.all((dt > 0.0099) & (dt < 0.301)))
    for key, other in (("mlp_hidden_act", "silu"), ("n_group", 8),
                       ("use_conv_bias", False), ("sliding_window", 4096),
                       ("hybrid_override_pattern", "ME-*E"),
                       ("experts_held", [12, 8])):
        with pytest.raises(ValueError, match=key.split("_")[0]):
            hybrid_ssm_tree.model_kwargs({**config, key: other}, 128)
    # the real configuration's fields, from its file
    real = hybrid_ssm_tree.model_kwargs(CONFIG, 4096)
    assert (real["pattern"], real["num_experts"], real["experts_held"],
            real["experts_per_token"], real["expert_latent"]) == (
                "MEMEMEMEM*E", 512, (0, 128), 22, 1024)


def test_the_cells_ask_for_the_issues_traffic():
    from benchmarks.lib import traffic

    seconds = float(BENCH["run_seconds"])
    cell = cell_file(CELL)
    mix = cell["traffic"]
    assert mix["prompt_tokens"] == {"median": 256, "sigma": 0.8, "lo": 32,
                                    "hi": 2048}
    assert mix["new_tokens"] == {"median": 128, "sigma": 0.6, "lo": 16,
                                 "hi": 512}
    assert (cell["job"]["slots"], cell["job"]["max_len"]) == (128, 4096)
    assert {cell["job"][k] for k in ("dtype", "param_dtype", "kv_dtype")} \
        == {"bfloat16"}
    trace = traffic.request_trace(1, mix, seconds, 32768, 4096)
    buckets = {max(8, 1 << (len(r["prompt"]) - 1).bit_length())
               for r in trace}
    assert buckets == {32, 64, 128, 256, 512, 1024, 2048}   # each is warmed
    assert sorted(cell["job"]["warm_prompt_lens"]) == sorted(buckets)
    assert max(len(r["prompt"]) - 1 + cell["check"]["pad_new"]
               for r in trace) <= cell["check"]["pad_to"]
    assert max(r["max_new_tokens"] for r in trace) <= cell["check"]["pad_new"]
    assert all(int(r["prompt"].max()) < 32768 for r in trace)

    gen = cell_file(GEN)
    assert gen["driver"] == "serve" and gen["config"] == "gpt2-large"
    assert gen["traffic"]["prompt_tokens"] == {"median": 32, "sigma": 0.5,
                                               "lo": 16, "hi": 64}
    assert gen["traffic"]["new_tokens"] == {"median": 448, "sigma": 0.4,
                                            "lo": 256, "hi": 768}
    chat = cell_file("serve-gpt2l-chat")
    assert {k: gen["job"][k] for k in ("slots", "dtype", "kv_dtype")} \
        == {k: chat["job"][k] for k in ("slots", "dtype", "kv_dtype")}
    assert gen["limits"] == chat["limits"]
    gen_trace = traffic.request_trace(1, gen["traffic"], seconds, 50257, 1024)
    assert {max(8, 1 << (len(r["prompt"]) - 1).bit_length())
            for r in gen_trace} == set(gen["job"]["warm_prompt_lens"])

    for name, which, vocab, max_len in ((CELL, cell, 32768, 4096),
                                        (GEN, gen, 50257, 1024)):
        def long_decodes_early(order_seed):     # PERF.md section 4's rule
            t = traffic.request_trace(
                1, {**which["traffic"], "order_seed": order_seed}, seconds,
                vocab, max_len)
            top = sorted(t, key=lambda r: -r["max_new_tokens"])[:3]
            return all(r["arrival_s"] < seconds / 2 for r in top)

        chosen = which["traffic"]["order_seed"]
        assert long_decodes_early(chosen), name
        assert not any(long_decodes_early(s) for s in range(chosen)), name


def test_the_cells_report_the_shares_of_the_peak_and_the_new_metrics():
    mine = {m["name"] for m in BENCH["per_layer"] if CELL in cells_of(m)}
    assert {"model.serve_mfu", "device.idle_share.serve",
            "kvcache.hybrid_decode_step_mbu",
            "kvcache.state_share_of_round_bytes", "moe.experts_touched_p50",
            "moe.expert_load_max_p95", "scheduler.batch_occupancy_p50",
            "model.expert_ffn_share"} <= mine
    # its pattern no longer means prefill; its reader is mla_moe_costs'
    assert not {"kvcache.prefill_share", "kvcache.decode_step_mbu"} & mine
    # ttft_p90_ms spread by 15% over six runs on the chip, three times half
    # its bound (PERF.md section 6): the cell is off its list and off the
    # lists of the four metrics that move it, as the kanana cell is off
    # itl_p95_ms's
    reports = {m["name"] for m in BENCH["end_to_end"] if CELL in cells_of(m)}
    assert reports == {"serve_tok_s", "setup_s"}
    assert not any(m["moves"] == "ttft_p90_ms" for m in BENCH["per_layer"]
                   if CELL in cells_of(m))
    chat = {m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]
            if "serve-gpt2l-chat" in m.get("workloads", [])}
    gen = {m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]
           if GEN in m.get("workloads", [])}
    assert chat - gen == {"kvcache.prefill_share"} and gen <= chat
    assert [w["chips"] for w in BENCH["workloads"]
            if w["name"] in (CELL, GEN)] == [1, 1]


def test_costs_count_the_experts_held_and_the_state_both_ways():
    from benchmarks.lib import hybrid_ssm_costs as costs
    from benchmarks.lib import hybrid_ssm_weights as weights

    # one prompt token alone, one generated token: no decode step is fed
    fixed = 5 * 109_576_192 + 35_651_584 + 5 * 54_525_952
    one = costs.serve_flops(CONFIG, 1, 1, 5.5)
    want = (2.0 * fixed + 5 * 4.0 * 128 * 64 * 128
            + 5 * 5.5 * 2.0 * 5_505_024 + 2.0 * 2 * 4096
            + 2.0 * 4096 * 32768)
    assert one == pytest.approx(want)
    # not the 22 a token chose: those lie on the other chips
    assert costs.serve_flops(CONFIG, 1, 1, 22) - one == pytest.approx(
        5 * 16.5 * 2.0 * 5_505_024)
    # a second generated token: one more token through the layers, two
    # keys behind it in the one attention layer, the head again
    two = costs.serve_flops(CONFIG, 1, 2, 5.5) - one
    assert two == pytest.approx(want - 2.0 * 2 * 4096 + 2 * 2.0 * 2 * 4096)
    # a round of 128 streams that touches every held expert moves every
    # weight but the embedding once (gains and per-head vectors apart),
    # each slot's state twice and its rows once
    parts = costs.decode_round_bytes(CONFIG, 128, 128, 300.0, 21_278_720,
                                     1024)
    small = 5 * (2 * 4096 + 5 * 10240 + 3 * 128 + 8192 - 4096) \
        + 4096 + 5 * (4096 + 512) + 4096
    assert parts["weights"] + parts["experts"] == 2 * (
        weights.param_count(CONFIG) - 32768 * 4096 - small)
    assert parts["state"] == 2 * 128 * 21_278_720
    assert parts["rows"] == 128 * 300.0 * 1024


def run_hybrid(**kw):
    return run_tiny("tiny-serve-hybrid-ssm", CELL, **kw)


def test_the_tiny_cell_runs_through_the_new_driver():
    result = run_hybrid()
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 4
    want = {m["name"] for m in BENCH["end_to_end"] if CELL in cells_of(m)}
    assert set(result["metrics"]) == want == {"serve_tok_s", "setup_s"}


def test_the_sample_holds_a_request_from_a_reused_slot_and_the_longest():
    import jax

    from benchmarks.drivers import serve_hybrid_ssm
    from benchmarks.lib import traffic
    from helpers import tiny

    _, cell, config = tiny("tiny-serve-hybrid-ssm", CELL)
    run = serve_hybrid_ssm.Run(cell, config, seed=9, seconds=2.0,
                               devices=jax.devices()[:1], note=lambda m: None)
    run.build()
    obs = run.serve(traffic.request_trace(9, run.mix, 2.0, run.vocab,
                                          run.max_len))
    assert len(run.slots_held) == obs["attempted"] > run.kv.slots
    assert 0 < obs["held_experts_per_token"] < 4     # 8 of 16 held, 4 chosen
    assert obs["state_bytes_per_slot"] == 2 * (8 * 16 * 16 * 4
                                               + 3 * 192 * 4)
    sample = run.sample()
    assert len(sample) == cell["check"]["sample_requests"]
    assert run.sampled_reused >= 1
    longest = max(run.finished, key=lambda f: len(f[0]["prompt"]) + len(f[1]))
    assert sample[0][0]["rid"] == longest[0]["rid"]


def test_the_new_readers_read_the_windows_records():
    """After a window of the tiny cell: a share of the memory roofline (a
    count against a stand-in peak here, not a device metric) and the
    state's share of the round's bytes; nothing where there is nothing."""
    from benchmarks import run as runmod
    from helpers import cpu_peaks, tiny

    result = run_hybrid(seed=11)
    assert result["correct"]
    bench, cell, config = tiny("tiny-serve-hybrid-ssm", CELL)
    ctx = {"config": config, "cell": cell, "chips": 1, "peaks": cpu_peaks(),
           "trace": None}
    obs = {"decode_context_mean": 30.0, "cache_bytes_per_token": 256,
           "state_bytes_per_slot": 20992}
    names = ("kvcache.hybrid_decode_step_mbu",
             "kvcache.state_share_of_round_bytes")
    entries = [m for m in bench["per_layer"] if m["name"] in names]
    got = runmod.evaluate(entries, obs, ctx)
    assert got[names[0]]["value"] > 0
    assert 0 < got[names[1]]["value"] < 100
    # no observation (an older driver), or another model's configuration
    assert runmod.evaluate(entries, {}, ctx) == {}
    other = {**ctx, "config": load("tiny-mla-moe")}
    assert runmod.evaluate(entries, obs, other) == {}
    # without a trace the device metric is left out, never 0
    share = [m for m in bench["per_layer"]
             if m["name"] == "model.ssm_mixer_share"]
    assert runmod.evaluate(share, obs, ctx) == {}


VARIANTS = [{"mode": "fp8"}] + [{"fault": f} for f in (
    "state_kept", "pads_advance", "tail_at_bucket_end", "no_d_skip",
    "no_dt_bias", "gate_after_norm", "relu_not_squared", "no_routed_scale",
    "no_shared")]


@pytest.fixture(scope="module")
def served():
    import jax

    from benchmarks.drivers import serve_hybrid_ssm
    from benchmarks.lib import traffic
    from helpers import tiny

    _, cell, config = tiny("tiny-serve-hybrid-ssm", CELL)
    run = serve_hybrid_ssm.Run(cell, config, seed=5, seconds=1.0,
                               devices=jax.devices()[:1], note=lambda m: None)
    run.build()
    run.serve(traffic.request_trace(5, run.mix, 1.0, run.vocab, run.max_len))
    return run, run.sample(), cell["limits"]["token_logit_gap"]


def test_every_planted_fault_is_among_the_variants():
    from benchmarks.lib import hybrid_ssm_reference

    assert [v["fault"] for v in VARIANTS[1:]] \
        == list(hybrid_ssm_reference.FAULTS)


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
    result = run_hybrid()
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
                        lambda name: tiny("tiny-serve-hybrid-ssm", name))
    monkeypatch.setattr(runmod, "require_devices",
                        lambda chips: (jax.devices()[:chips], cpu_peaks()))
    monkeypatch.setattr(calibrate, "OUT", tmp_path)
    assert calibrate_mla_moe.main(
        ["--workload", CELL, "--seeds", "5,8", "--controls", "1",
         "--fault-seeds", "1", "--faults", "state_kept,pads_advance",
         "--seconds", "1.0"]) == 0
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    assert [(l["seed"], l["who"]) for l in lines] == [
        (5, "program"), (5, "control_fp8"), (5, "fault_state_kept"),
        (5, "fault_pads_advance"), (8, "program")]
    assert lines[0]["token_logit_gap"] < 0.001 < min(
        l["token_logit_gap"] for l in lines[1:4])

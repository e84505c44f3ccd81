"""The latent-attention, sparse-expert configuration's files: the tiny cell
through the new driver, its comparison against control and planted faults,
the cost functions against the weights that are made, the new readers."""

from __future__ import annotations

import json

import pytest

from helpers import ROOT, load, run_tiny

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = "serve-kanana2-longdoc"
CONFIG = json.loads((ROOT / "benchmarks/configs/kanana-2-30b-a3b-6l.json")
                    .read_text())


def cells_of(metric: dict) -> list[str]:
    return metric.get("workloads", [w["name"] for w in BENCH["workloads"]])


def test_the_configuration_holds_the_published_keys():
    """Every number of the catalog row under its own key; the depth alone
    differs and is listed; no width is among what was cut."""
    published = {
        "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
        "kv_lora_rank": 512, "max_position_embeddings": 32768,
        "model_type": "deepseek_v3", "moe_intermediate_size": 768,
        "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 128,
        "n_shared_experts": 2, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 6,
        "num_hidden_layers": 48, "num_key_value_heads": 32,
        "q_lora_rank": None, "qk_head_dim": 192, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
        "rope_interleave": True, "rope_scaling": None, "rope_theta": 1000000,
        "routed_scaling_factor": 2.448, "scoring_func": "sigmoid",
        "tie_word_embeddings": False, "topk_group": 1,
        "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 128256}
    entry = next(c for c in BENCH["configs"] if c["file"].endswith(
        "kanana-2-30b-a3b-6l.json"))
    differs = {k for k, v in published.items() if CONFIG[k] != v}
    assert differs == set(entry["reduced"]) == {"num_hidden_layers"}
    assert CONFIG["num_hidden_layers"] == 6
    assert CONFIG["_published"] == {"num_hidden_layers": 48}
    assert entry["source"] == CONFIG["_source"]
    assert "7 further chips" in CONFIG["deployment"]


def test_the_arithmetic_of_the_cut():
    from benchmarks.lib import mla_moe_costs, mla_moe_weights

    assert mla_moe_weights.param_count(CONFIG) == 3_789_584_000
    assert mla_moe_costs.attention_params(CONFIG) == 26_345_472
    assert mla_moe_costs.expert_params(CONFIG) * 128 == 603_979_776
    whole = {**CONFIG, "num_hidden_layers": 48}
    assert 30.6e9 < mla_moe_weights.param_count(whole) < 30.7e9
    # the table: 576 values a token a layer
    cell = json.loads((ROOT / "benchmarks/workloads" / f"{CELL}.json")
                      .read_text())
    per_token = 6 * (512 + 64) * 2
    assert cell["job"]["slots"] * cell["job"]["max_len"] * per_token \
        == 1_811_939_328


def test_the_cell_asks_for_the_issues_traffic():
    cell = json.loads((ROOT / "benchmarks/workloads" / f"{CELL}.json")
                      .read_text())
    mix = cell["traffic"]
    assert mix["prompt_tokens"] == {"median": 3072, "sigma": 0.5,
                                    "lo": 1024, "hi": 7168}
    assert mix["new_tokens"] == {"median": 128, "sigma": 0.6, "lo": 32,
                                 "hi": 512}
    assert cell["job"]["slots"] == 32 and cell["job"]["max_len"] == 8192
    assert isinstance(mix["rate_per_s"], float)
    from benchmarks.lib import traffic

    trace = traffic.request_trace(1, mix, float(BENCH["run_seconds"]),
                                  128256, 8192)
    buckets = {1 << (len(r["prompt"]) - 1).bit_length() for r in trace}
    assert buckets == {1024, 2048, 4096, 8192}      # each is warmed
    assert sorted(cell["job"]["warm_prompt_lens"]) == [1024, 2048, 4096,
                                                       7168]
    assert all(len(r["prompt"]) + r["max_new_tokens"] <= 8192 for r in trace)

    def long_decodes_early(order_seed):     # PERF.md section 4's rule
        t = traffic.request_trace(1, {**mix, "order_seed": order_seed},
                                  float(BENCH["run_seconds"]), 128256, 8192)
        top = sorted(t, key=lambda r: -r["max_new_tokens"])[:3]
        return all(r["arrival_s"] < BENCH["run_seconds"] / 2 for r in top)

    assert long_decodes_early(mix["order_seed"])
    assert not any(long_decodes_early(s) for s in range(mix["order_seed"]))


def test_the_cell_reports_the_shares_of_the_peak_and_the_new_metrics():
    mine = {m["name"] for m in BENCH["per_layer"] if CELL in cells_of(m)}
    assert {"model.serve_mfu", "device.idle_share.serve",
            "kvcache.decode_step_mbu", "moe.experts_touched_p50",
            "moe.expert_load_max_p95"} <= mine
    assert "kvcache.prefill_share" not in mine      # it reads the scan's while
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert [w["name"] for w in four] == ["train-gpt2m-1k-dp4"]


def test_serve_flops_count_active_parameters_and_both_attention_forms():
    from benchmarks.lib import mla_moe_costs as costs

    s = costs._sizes(CONFIG)
    # one prompt token alone, one generated token: no decode step is fed
    one = costs.serve_flops(CONFIG, 1, 1)
    per_key = 32 * (128 + 64 + 128)
    want = (costs.ffn_flops_per_token(CONFIG)
            + 6 * 2.0 * (costs.attention_params(CONFIG) + per_key)
            + 2.0 * 2048 * 128256)
    assert one == pytest.approx(want)
    # a fed decode token reads the latents: 2 r + d_r a head a key
    two = costs.serve_flops(CONFIG, 1, 2) - one
    absorbed = 6 * 2.0 * (costs.attention_params(CONFIG)
                          + 32 * (2 * 512 + 64) * 2)
    assert two == pytest.approx(costs.ffn_flops_per_token(CONFIG) + absorbed
                                + 2.0 * 2048 * 128256)
    active = costs.ffn_flops_per_token(CONFIG) / 2.0
    assert active == 3 * 2048 * 6144 + 5 * (
        2048 * 128 + 6 * 3 * 2048 * 768 + 3 * 2048 * 1536)
    assert s["k"] == 6 and s["e"] == 128
    # a round that touches every expert reads every weight but the
    # embedding, once
    from benchmarks.lib import mla_moe_weights

    everything = costs.decode_round_bytes(CONFIG, 128, 0, 0)
    assert everything == 2 * (mla_moe_weights.param_count(CONFIG)
                              - 128256 * 2048 - 29_696 - 5 * 128)


def run_mla_moe(**kw):
    return run_tiny("tiny-serve-mla-moe", CELL, **kw)


def test_the_tiny_cell_runs_through_the_new_driver():
    result = run_mla_moe()
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 4
    want = {m["name"] for m in BENCH["end_to_end"] if CELL in cells_of(m)}
    # not the 95th percentile of the gaps between tokens: it spread by 5.9%
    # over seven runs on the chip, more than half its bound (PERF.md)
    assert set(result["metrics"]) == want == {
        "serve_tok_s", "ttft_p90_ms", "setup_s"}


def test_the_new_readers_read_the_windows_records():
    """After a window of the tiny cell: the routing attributes of its
    decode rounds, and a share of the memory roofline (a count against a
    stand-in peak here, not a device metric)."""
    from benchmarks import run as runmod
    from helpers import cpu_peaks, tiny

    result = run_mla_moe(seed=11)
    assert result["correct"]
    bench, cell, config = tiny("tiny-serve-mla-moe", CELL)
    ctx = {"config": config, "cell": cell, "chips": 1, "peaks": cpu_peaks(),
           "trace": None}
    obs = {"decode_context_mean": 30.0, "cache_bytes_per_token": 480}
    entries = [m for m in bench["per_layer"]
               if m["name"].startswith(("moe.", "kvcache.decode_step_mbu"))]
    got = runmod.evaluate(entries, obs, ctx)
    assert 1 <= got["moe.experts_touched_p50"]["value"] <= 16
    assert 1 <= got["moe.expert_load_max_p95"]["value"] <= 4
    assert got["kvcache.decode_step_mbu"]["value"] > 0
    # nothing to read: no observation, or a program without the attribute
    assert "kvcache.decode_step_mbu" not in runmod.evaluate(entries, {}, ctx)
    from benchmarks.lib import moe_readers

    assert moe_readers.attr_percentile(
        {"root": "serve_run", "span": "decode_step", "attr": "no_such",
         "q": 50}, {}, ctx) is None


@pytest.mark.parametrize("variant", [{"mode": "fp8"}, {"fault": "no_shared"},
                                     {"fault": "no_routed_scale"},
                                     {"fault": "k_rope_unrotated"},
                                     {"fault": "bias_ignored"}],
                         ids=lambda v: next(iter(v.values())))
def test_the_control_and_the_planted_faults_fail_the_limit(variant):
    """The served tokens of one window, judged by the reference as it is
    (inside the limit) and by the float8 control or a planted fault (the
    token that variant puts first lies below the reference's best by more
    than the limit)."""
    import jax

    from benchmarks.drivers import serve_mla_moe
    from benchmarks.lib import traffic
    from helpers import tiny

    _, cell, config = tiny("tiny-serve-mla-moe", CELL)
    run = serve_mla_moe.Run(cell, config, seed=5, seconds=1.0,
                            devices=jax.devices()[:1], note=lambda m: None)
    run.build()
    run.serve(traffic.request_trace(5, run.mix, 1.0, run.vocab, run.max_len))
    sample = run.sample()
    limit = cell["limits"]["token_logit_gap"]
    assert run.gaps(sample)["token_logit_gap"] <= limit
    assert run.gaps(sample, **variant)["token_logit_gap"] > limit


def test_near_ties_are_set_apart_and_counted():
    """With a margin no position clears, nothing is compared and the share
    set apart is all of it: the share's own limit fails, the gap's cannot
    be met by exclusion."""
    def wide(cell):
        cell["check"]["near_tie_margin"] = 1.0
        cell["limits"]["near_tie_share"] = 0.5

    result = run_mla_moe(patch_cell=wide)
    assert not result["correct"]
    assert result["checks"]["near_tie_share"] == {"value": 1.0, "limit": 0.5}
    assert result["checks"]["token_logit_gap"]["value"] == 0.0

    def narrow(cell):
        cell["check"]["near_tie_margin"] = 0.002
        cell["limits"]["near_tie_share"] = 0.5

    result = run_mla_moe(patch_cell=narrow)
    assert result["correct"], result["checks"]
    assert 0.0 < result["checks"]["near_tie_share"]["value"] < 0.5


def test_a_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    from distributed_tensorflow_tpu.serving.kv_cache import SlotKVCache

    real = SlotKVCache.advance

    def altered(self, *a, **k):
        out = real(self, *a, **k).copy()
        out[0] = (out[0] + 1) % 500
        return out

    monkeypatch.setattr(SlotKVCache, "advance", altered)
    result = run_mla_moe()
    assert not result["correct"], result["checks"]


def test_the_tree_mapping_relabels_and_copies_nothing():
    import jax

    from benchmarks.drivers import mla_moe_tree
    from benchmarks.lib import mla_moe_weights

    config = load("tiny-mla-moe")
    weights = mla_moe_weights.make(config, 3)
    tree = mla_moe_tree.to_flax(weights)
    assert {id(leaf) for leaf in jax.tree.leaves(tree)} \
        == {id(leaf) for leaf in jax.tree.leaves(weights)}
    from distributed_tensorflow_tpu.models import create_model

    model = create_model("mla_moe", dtype="bfloat16", param_dtype="bfloat16",
                         **mla_moe_tree.model_kwargs(config, 128))
    import jax.numpy as jnp

    want = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((1, 4), jnp.int32)))["params"]
    assert jax.tree.map(lambda t: (t.shape, str(t.dtype)), tree) \
        == jax.tree.map(lambda t: (t.shape, str(t.dtype)), want)
    with pytest.raises(ValueError, match="q_lora_rank"):
        mla_moe_tree.model_kwargs({**config, "q_lora_rank": 1536}, 128)


def test_the_calibration_reads_program_control_and_faults(monkeypatch,
                                                          capsys, tmp_path):
    """``calibrate_mla_moe.py`` end to end on the tiny cell (the look for
    a chip taken out): a line a seed for the program, then the control and
    the faults on the first seed, each with its curve over the margins."""
    import jax

    from benchmarks import calibrate, calibrate_mla_moe, run as runmod
    from helpers import cpu_peaks, tiny

    monkeypatch.setattr(runmod, "load_cell",
                        lambda name: tiny("tiny-serve-mla-moe", name))
    monkeypatch.setattr(runmod, "require_devices",
                        lambda chips: (jax.devices()[:chips], cpu_peaks()))
    monkeypatch.setattr(calibrate, "OUT", tmp_path)
    assert calibrate_mla_moe.main(
        ["--workload", CELL, "--seeds", "5,8", "--controls", "2",
         "--fault-seeds", "1", "--faults", "no_shared", "--seconds",
         "1.0"]) == 0
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    assert [(l["seed"], l["who"]) for l in lines] == [
        (5, "program"), (5, "control_fp8"), (5, "fault_no_shared"),
        (8, "program"), (8, "control_fp8")]
    program, control, fault = lines[:3]
    assert program["token_logit_gap"] < 0.001 < min(
        control["token_logit_gap"], fault["token_logit_gap"])
    curve = program["margin_gap_share"]
    assert curve[0][0] == 0.0 and curve[0][2] == 0.0
    assert [c[2] for c in curve] == sorted(c[2] for c in curve)

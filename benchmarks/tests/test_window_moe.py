"""The window-and-global-attention, sparse-expert configuration's files: the
tiny cell through the new driver, its comparison against control and
planted faults, the configuration against the catalog row, the cost
functions against the weights that are made, the cell's traffic and its
``order_seed`` rule, the new readers."""

from __future__ import annotations

import json

import pytest

from helpers import ROOT, load, run_tiny

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = "serve-commandaplus-mixedlen"
NAME = "command-a-plus-05-2026-4l-ep8"
CONFIG = json.loads((ROOT / f"benchmarks/configs/{NAME}.json").read_text())
PERIOD = ["sliding_attention"] * 3 + ["full_attention"]


def cells_of(metric: dict) -> list[str]:
    return metric.get("workloads", [w["name"] for w in BENCH["workloads"]])


def cell_file(name: str = CELL) -> dict:
    return json.loads((ROOT / "benchmarks/workloads" / f"{name}.json")
                      .read_text())


def test_the_configuration_holds_the_published_keys():
    """Every key of the catalog row under its own name; depth (with the
    layer types), experts held and vocabulary differ and are listed with
    the published values beside them; no width is among what was cut."""
    published = {
        "attention_bias": False, "expert_selection_fn": "sigmoid",
        "first_k_dense_replace": 0, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 4096, "intermediate_size": 4096,
        "layer_norm_eps": 1e-05, "layer_switch": 4,
        "layer_types": PERIOD * 8, "logit_scale": 1,
        "max_position_embeddings": 200000, "model_type": "cohere2_moe",
        "norm_topk_prob": True, "num_attention_heads": 128,
        "num_experts": 128, "num_experts_per_tok": 8,
        "num_hidden_layers": 32, "num_key_value_heads": 8,
        "num_shared_experts": 4,
        "order_of_interleaved_layers": "local_attn_first",
        "position_embedding_type": "rope_gptj",
        "prefix_dense_intermediate_size": 16384,
        "prefix_dense_sliding_window_pattern": 1, "rms_norm_eps": None,
        "rope_parameters": {"rope_theta": 50000, "rope_type": "default"},
        "rope_theta": 50000, "rotary_pct": 1,
        "shared_expert_combination_strategy": "average",
        "sliding_window": 4096, "tf_legacy_loss": False,
        "tie_word_embeddings": True, "use_embedding_sharing": True,
        "use_gated_activation": True, "use_parallel_block": True,
        "use_parallel_embedding": False, "use_qk_norm": False,
        "vocab_size": 262144}
    entry = next(c for c in BENCH["configs"] if c["name"] == NAME)
    differs = {k for k, v in published.items() if CONFIG[k] != v}
    assert differs == set(entry["reduced"]) == {
        "num_hidden_layers", "layer_types", "num_experts", "vocab_size"}
    assert CONFIG["_published"] == {k: published[k] for k in differs}
    assert set(CONFIG["_reduced_why"]) == differs
    assert CONFIG["layer_types"] == PERIOD
    assert (CONFIG["num_hidden_layers"], CONFIG["num_experts"],
            CONFIG["experts_held"], CONFIG["vocab_size"]) == (
                4, 16, [0, 16], 32768)
    assert entry["source"] == CONFIG["_source"]
    assert entry["file"] == f"benchmarks/configs/{NAME}.json"
    assert "eight chips share each layer" in CONFIG["deployment"]
    assert {"expert_width", "shared_average", "global_nope",
            "initializer_range", "weights", "held_dtype", "max_len",
            "vision", "unread", "parameters"} <= set(CONFIG["assumed"])


def test_the_arithmetic_of_the_cut():
    from benchmarks.lib import window_moe_costs as costs
    from benchmarks.lib import window_moe_weights as weights

    assert weights.param_count(CONFIG) == 4_733_292_544
    assert weights.layer_params(CONFIG) == 1_149_767_680
    assert costs.expert_params(CONFIG) == 50_331_648
    assert costs.outside_experts(CONFIG) == 344_461_312 - 4_096
    whole = {**CONFIG, **CONFIG["_published"]}
    assert 218.2e9 < weights.param_count(whole) < 218.3e9
    job = cell_file()["job"]
    slot = 3 * 4096 * 4096 + 16384 * 4096
    assert slot == 117_440_512
    assert job["slots"] * slot == 3_758_096_384
    assert job["slots"] * 4 * job["max_len"] * 4096 == 8_589_934_592


def test_the_tree_mapping_relabels_copies_nothing_and_checks_its_keys():
    import jax
    import jax.numpy as jnp

    from benchmarks.drivers import window_moe_tree
    from benchmarks.lib import window_moe_weights
    from distributed_tensorflow_tpu.models import create_model

    config = load("tiny-window-moe")
    weights = window_moe_weights.make(config, 3)
    tree = window_moe_tree.to_flax(weights)
    made = {id(leaf) for leaf in jax.tree.leaves(weights)}
    extra = [leaf for leaf in jax.tree.leaves(tree) if id(leaf) not in made]
    # the family has no choice bias: the layer's is handed zeros
    assert len(extra) == 4 and all(not leaf.any() for leaf in extra)
    model = create_model("window_moe", dtype="bfloat16",
                         param_dtype="bfloat16",
                         **window_moe_tree.model_kwargs(config, 128))
    assert model.experts_held == (0, 8) and model.num_experts == 16
    want = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((1, 4), jnp.int32)))["params"]
    assert jax.tree.map(lambda t: (t.shape, str(t.dtype)), tree) \
        == jax.tree.map(lambda t: (t.shape, str(t.dtype)), want)
    gains = weights["layers"][0]["norm"].astype(jnp.float32)
    assert 0.5 < float(gains.min()) < float(gains.max()) < 1.5
    for key, other in (
            ("model_type", "cohere2"), ("use_parallel_block", False),
            ("use_qk_norm", True), ("attention_bias", True),
            ("use_gated_activation", False), ("hidden_act", "gelu"),
            ("expert_selection_fn", "softmax"),
            ("shared_expert_combination_strategy", "sum"),
            ("position_embedding_type", "rope_gptneox"), ("rotary_pct", 0.5),
            ("tie_word_embeddings", False), ("first_k_dense_replace", 1),
            ("order_of_interleaved_layers", "global_attn_first"),
            ("layer_types", ["chunked_attention"] * 4),
            ("experts_held", [12, 8])):
        with pytest.raises(ValueError, match=key.split("_")[0]):
            window_moe_tree.model_kwargs({**config, key: other}, 128)
    assert set(window_moe_tree.FIXED) == {
        "model_type", "use_parallel_block", "use_qk_norm", "attention_bias",
        "use_gated_activation", "hidden_act", "expert_selection_fn",
        "shared_expert_combination_strategy", "position_embedding_type",
        "rotary_pct", "tie_word_embeddings", "first_k_dense_replace",
        "order_of_interleaved_layers"}
    # the real configuration's fields, from its file
    real = window_moe_tree.model_kwargs(CONFIG, 16384)
    assert (real["pattern"], real["window"], real["num_experts"],
            real["experts_held"], real["experts_per_token"],
            real["shared_experts"], real["heads"], real["kv_heads"]) == (
                "WWWF", 4096, 128, (0, 16), 8, 4, 128, 8)


def order_rule(mix: dict, order_seed: int, seconds: float) -> bool:
    """PERF.md section 4's rule (the three longest decodes, ties to the
    earliest arrival, all due in the first half of the window) AND none of
    the ten longest decodes among the last tenth of the arrivals."""
    from benchmarks.lib import traffic

    t = traffic.request_trace(1, {**mix, "order_seed": order_seed}, seconds,
                              32768, 16384)
    longest = sorted(t, key=lambda r: -r["max_new_tokens"])
    last = {r["rid"] for r in t[len(t) - len(t) // 10:]}
    return all(r["arrival_s"] < seconds / 2 for r in longest[:3]) \
        and not any(r["rid"] in last for r in longest[:10])


def test_the_cell_asks_for_the_issues_traffic():
    from benchmarks.lib import traffic

    seconds = float(BENCH["run_seconds"])
    cell = cell_file()
    mix = cell["traffic"]
    assert mix["prompt_tokens"] == {"median": 2048, "sigma": 1.1, "lo": 128,
                                    "hi": 12288}
    assert mix["new_tokens"] == {"median": 160, "sigma": 0.6, "lo": 32,
                                 "hi": 512}
    assert (cell["job"]["slots"], cell["job"]["max_len"]) == (32, 16384)
    assert {cell["job"][k] for k in ("dtype", "param_dtype", "kv_dtype")} \
        == {"bfloat16"}
    trace = traffic.request_trace(1, mix, seconds, 32768, 16384)
    bucket = lambda lp: max(8, 1 << (lp - 1).bit_length())
    buckets = {bucket(len(r["prompt"])) for r in trace}
    warmed = {bucket(lp) for lp in cell["job"]["warm_prompt_lens"]}
    assert warmed == {128, 256, 512, 1024, 2048, 4096, 8192, 16384}
    assert len(cell["job"]["warm_prompt_lens"]) == 8      # each warmed once
    # (the 128 bucket is reached only where a prompt is clipped to lo)
    assert warmed - {128} <= buckets <= warmed
    assert max(len(r["prompt"]) - 1 + cell["check"]["pad_new"]
               for r in trace) <= cell["check"]["pad_to"]
    assert max(r["max_new_tokens"] for r in trace) <= cell["check"]["pad_new"]
    assert all(int(r["prompt"].max()) < 32768 for r in trace)
    # short and long in one queue: a quarter of the prompts pass the window
    past = sum(len(r["prompt"]) > 4096 for r in trace) / len(trace)
    assert 0.2 < past < 0.32
    assert sum(len(r["prompt"]) < 512 for r in trace) / len(trace) > 0.08
    # the longest request's context passes the window by more than 512
    assert max(len(r["prompt"]) + r["max_new_tokens"] for r in trace) \
        > 4096 + 512
    chosen = mix["order_seed"]
    assert order_rule(mix, chosen, seconds)
    assert not any(order_rule(mix, s, seconds) for s in range(chosen))


def test_the_cell_reports_the_shares_of_the_peak_and_the_new_metrics():
    mine = {m["name"] for m in BENCH["per_layer"] if CELL in cells_of(m)}
    assert mine >= {
        "model.serve_mfu", "device.idle_share.serve",
        "kvcache.window_decode_step_mbu",
        "kvcache.window_share_of_round_bytes", "moe.experts_touched_p50",
        "moe.expert_load_max_p95", "scheduler.batch_occupancy_p50",
        "model.expert_ffn_share"}
    # the two older models' round readers count other leaves
    assert not {"kvcache.hybrid_decode_step_mbu",
                "kvcache.decode_step_mbu"} & mine
    reports = {m["name"] for m in BENCH["end_to_end"] if CELL in cells_of(m)}
    assert {"serve_tok_s", "setup_s"} <= reports
    assert [w["chips"] for w in BENCH["workloads"] if w["name"] == CELL] \
        == [1]
    new = [m for m in BENCH["per_layer"]
           if m["name"].startswith("kvcache.window_")]
    assert [m["workloads"] for m in new] == [[CELL], [CELL]]
    assert {m["moves"] for m in new} == {"serve_tok_s"}


def test_costs_count_the_experts_held_and_the_keys_seen():
    from benchmarks.lib import window_moe_costs as costs
    from benchmarks.lib import window_moe_weights as weights

    fixed = 4 * (344_461_312 - 4_096)
    # one prompt token alone, one generated token: no decode step is fed;
    # one key seen in each of the four layers
    one = costs.serve_flops(CONFIG, 1, 1, 1.0)
    want = 2.0 * fixed + 4 * 1.0 * 2.0 * 50_331_648 \
        + 4 * 2.0 * 2 * 16384 + 2.0 * 4096 * 32768
    assert one == pytest.approx(want)
    # not the 8 a token chose: 7 of them lie on the other chips
    assert costs.serve_flops(CONFIG, 1, 1, 8) - one == pytest.approx(
        4 * 7 * 2.0 * 50_331_648)
    # past the window a query sees 4,096 keys in the three window layers
    # and all of them in the full one
    assert costs.keys_seen(5000, 4096) == 4096 * 4097 / 2 + 904 * 4096
    assert costs.keys_seen(5000, None) == 5000 * 5001 / 2
    assert costs.keys_seen(100, 4096) == costs.keys_seen(100, None) == 5050
    longer = costs.serve_flops(CONFIG, 5000, 2, 1.0) \
        - costs.serve_flops(CONFIG, 5000, 1, 1.0)
    assert longer == pytest.approx(
        want - 4 * 2.0 * 2 * 16384
        + (3 * 4096 + 5001) * 2.0 * 2 * 16384)
    # a round that touches every held expert moves every weight once
    # (gains apart), and the rows and ring rows behind its streams
    parts = costs.decode_round_bytes(CONFIG, 16, 32 * 6000.0, 32 * 4096.0,
                                     4096, 3 * 4096)
    assert parts["weights"] + parts["experts"] == 2 * (
        weights.param_count(CONFIG) - 5 * 4096)
    assert parts["rows"] == 32 * 6000.0 * 4096
    assert parts["rings"] == 32 * 4096.0 * 3 * 4096


def run_window(**kw):
    return run_tiny("tiny-serve-window-moe", CELL, **kw)


def test_the_tiny_cell_runs_through_the_new_driver():
    result = run_window()
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 4
    want = {m["name"] for m in BENCH["end_to_end"] if CELL in cells_of(m)}
    assert set(result["metrics"]) == want >= {"serve_tok_s", "setup_s"}


def test_the_sample_holds_the_longest_and_one_after_a_longer_occupant():
    import jax

    from benchmarks.drivers import serve_window_moe
    from benchmarks.lib import traffic
    from helpers import tiny

    _, cell, config = tiny("tiny-serve-window-moe", CELL)
    run = serve_window_moe.Run(cell, config, seed=9, seconds=2.0,
                               devices=jax.devices()[:1], note=lambda m: None)
    run.build()
    obs = run.serve(traffic.request_trace(9, run.mix, 2.0, run.vocab,
                                          run.max_len))
    assert len(run.slots_held) == obs["attempted"] > run.kv.slots
    assert 0 < obs["held_experts_per_token"] < 4     # 8 of 16 held, 4 chosen
    assert obs["window_bytes_per_slot"] == 3 * 2 * 16 * 2 * 16 * 4
    assert obs["cache_bytes_per_token"] == 2 * 2 * 16 * 4
    assert obs["ring_rows"] == 16
    assert obs["decode_context_mean_below_window"] <= 16 \
        < obs["decode_context_mean"]
    sample = run.sample()
    assert len(sample) == cell["check"]["sample_requests"]
    assert run.sampled_after_longer >= 1 and run.sampled_past_window >= 1
    longest = max(run.finished, key=lambda f: len(f[0]["prompt"]) + len(f[1]))
    assert sample[0][0]["rid"] == longest[0]["rid"]


def test_the_new_readers_read_the_windows_records():
    """After a window of the tiny cell: a share of the memory roofline (a
    count against a stand-in peak here, not a device metric) and the
    rings' share of the round's bytes; nothing where there is nothing."""
    from benchmarks import run as runmod
    from helpers import cpu_peaks, tiny

    result = run_window(seed=11)
    assert result["correct"]
    bench, cell, config = tiny("tiny-serve-window-moe", CELL)
    ctx = {"config": config, "cell": cell, "chips": 1, "peaks": cpu_peaks(),
           "trace": None}
    obs = {"decode_context_mean": 30.0,
           "decode_context_mean_below_window": 9.0,
           "cache_bytes_per_token": 256, "window_bytes_per_slot": 12288,
           "ring_rows": 16}
    names = ("kvcache.window_decode_step_mbu",
             "kvcache.window_share_of_round_bytes")
    entries = [m for m in bench["per_layer"] if m["name"] in names]
    got = runmod.evaluate(entries, obs, ctx)
    assert got[names[0]]["value"] > 0
    assert 0 < got[names[1]]["value"] < 100
    # no observation (an older driver), or another model's configuration
    assert runmod.evaluate(entries, {}, ctx) == {}
    other = {**ctx, "config": load("tiny-mla-moe")}
    assert runmod.evaluate(entries, obs, other) == {}


VARIANTS = [{"mode": "fp8"}] + [{"fault": f} for f in (
    "window_off", "window_one_wider", "rope_in_full", "rope_half_split",
    "ring_row_rotation", "pads_in_ring", "ring_kept", "shared_summed",
    "sequential_block", "no_gain")]


@pytest.fixture(scope="module")
def served():
    import jax

    from benchmarks.drivers import serve_window_moe
    from benchmarks.lib import traffic
    from helpers import tiny

    _, cell, config = tiny("tiny-serve-window-moe", CELL)
    run = serve_window_moe.Run(cell, config, seed=5, seconds=2.0,
                               devices=jax.devices()[:1], note=lambda m: None)
    run.build()
    run.serve(traffic.request_trace(5, run.mix, 2.0, run.vocab, run.max_len))
    return run, run.sample(), cell["limits"]["token_logit_gap"]


def test_every_planted_fault_is_among_the_variants():
    from benchmarks.lib import window_moe_reference

    assert [v["fault"] for v in VARIANTS[1:]] \
        == list(window_moe_reference.FAULTS)


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
    result = run_window()
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
                        lambda name: tiny("tiny-serve-window-moe", name))
    monkeypatch.setattr(runmod, "require_devices",
                        lambda chips: (jax.devices()[:chips], cpu_peaks()))
    monkeypatch.setattr(calibrate, "OUT", tmp_path)
    assert calibrate_mla_moe.main(
        ["--workload", CELL, "--seeds", "5,8", "--controls", "1",
         "--fault-seeds", "1", "--faults", "ring_kept,pads_in_ring",
         "--seconds", "1.0"]) == 0
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    assert [(l["seed"], l["who"]) for l in lines] == [
        (5, "program"), (5, "control_fp8"), (5, "fault_ring_kept"),
        (5, "fault_pads_in_ring"), (8, "program")]
    assert lines[0]["token_logit_gap"] < 0.001 < min(
        l["token_logit_gap"] for l in lines[1:4])

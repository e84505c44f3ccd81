"""The sparse-attention / softmax-expert configuration's files: the tiny
cell through the new driver, its comparison against control and planted
faults, the configuration against the catalog row key by key, the cost
functions against the weights that are made, the new readers, the cell's
traffic rule."""

from __future__ import annotations

import json

import pytest

from helpers import ROOT, load, run_tiny

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL, NAME = "serve-keye2-64k", "keye-vl-2.0-30b-a3b-4l"
CONFIG = json.loads((ROOT / f"benchmarks/configs/{NAME}.json").read_text())
# the catalog's row (the model-configs guide's architectures.jsonl,
# ``Keye-VL-2.0-30B-A3B``: its ``config``)
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 262144, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "KeyeVL2",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4,
    "num_local_experts": 128, "rms_norm_eps": 1e-06,
    "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default",
                     "type": "default"},
    "rope_theta": 10000000,
    "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 2048},
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}
FAULTS = ("no_index_rope", "no_qk_norm", "no_norm_topk")     # the reference's
CHECKS = ("token_logit_gap", "selection_gap", "attend_gap")


def cells_of(metric: dict) -> list[str]:
    return metric.get("workloads", [w["name"] for w in BENCH["workloads"]])


def cell_file(name: str = CELL) -> dict:
    return json.loads((ROOT / "benchmarks/workloads" / f"{name}.json")
                      .read_text())


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_the_configuration_holds_the_published_key(key):
    want = 4 if key == "num_hidden_layers" else PUBLISHED[key]
    assert key in CONFIG and CONFIG[key] == want
    assert type(CONFIG[key]) is type(want)


def test_only_the_depth_is_reduced_and_what_is_assumed_is_listed():
    entry = next(c for c in BENCH["configs"] if c["name"] == NAME)
    assert entry["reduced"] == ["num_hidden_layers"]
    assert CONFIG["_published"]["num_hidden_layers"] == 48
    assert entry["source"] == CONFIG["_source"] == (
        "https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/"
        "config.json")
    assert set(CONFIG) - set(PUBLISHED) == {
        "_name", "_source", "_published", "_reduced_why", "deployment",
        "assumed"}
    assert {"initializer_range", "weights", "qk_norm", "rope",
            "index_rope_dim", "index_rope", "index_key_norm",
            "index_weight_scale", "index_key_dtype", "max_len", "vision",
            "parameters"} <= set(CONFIG["assumed"])
    assert CONFIG["assumed"]["index_rope_dim"] == 32


def test_the_arithmetic_of_the_whole_model():
    from benchmarks.lib import keye_costs as costs
    from benchmarks.lib import keye_weights as weights

    assert weights.layer_params(CONFIG) == 625_381_760
    assert weights.param_count(CONFIG) == 3_123_858_944
    assert weights.param_count({**CONFIG, "num_hidden_layers": 48}) \
        == 30_640_656_384
    m = costs.layer_matrices(CONFIG)
    assert (m["attention"], m["indexer"], m["router"], m["experts"]) == (
        18_874_368, 2_261_120 - 128, 262_144, 603_979_776)
    # what a slot keeps a token: K and V of 4 heads of 128 and one indexer
    # key of 64, four layers, bfloat16
    cell = cell_file()
    per_token = 4 * (2 * 4 * 128 * 2 + 64 * 2)
    assert per_token == 8704
    table = cell["job"]["slots"] * cell["job"]["max_len"] * per_token
    assert table == 4_563_402_752
    assert 2 * weights.param_count(CONFIG) + table < 0.7 * 16e9


def test_the_tree_mapping_relabels_copies_nothing_and_checks_its_keys():
    import jax
    import jax.numpy as jnp

    from benchmarks.drivers import keye_tree
    from benchmarks.lib import keye_weights
    from distributed_tensorflow_tpu.models import create_model

    config = load("tiny-keye")
    weights = keye_weights.make(config, 3)
    tree = keye_tree.to_flax(weights)
    assert {id(leaf) for leaf in jax.tree.leaves(tree)} \
        == {id(leaf) for leaf in jax.tree.leaves(weights)}
    model = create_model("keye", dtype="bfloat16", param_dtype="bfloat16",
                         **keye_tree.model_kwargs(config, 128))
    want = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((1, 4), jnp.int32)))["params"]
    assert jax.tree.map(lambda t: (t.shape, str(t.dtype)), tree) \
        == jax.tree.map(lambda t: (t.shape, str(t.dtype)), want)
    for key, other in (("hidden_act", "gelu"), ("attention_bias", True),
                       ("tie_word_embeddings", True), ("mlp_only_layers", [0]),
                       ("decoder_sparse_step", 2), ("norm_topk_prob", False)):
        with pytest.raises(ValueError, match=key):
            keye_tree.model_kwargs({**config, key: other}, 128)
    with pytest.raises(ValueError, match="positions"):
        keye_tree.model_kwargs(config, 512)
    real = keye_tree.model_kwargs(CONFIG, 65536)
    assert real == {
        "vocab_size": 151936, "hidden": 2048, "layers": 4, "heads": 32,
        "kv_heads": 4, "head_dim": 128, "index_heads": 16,
        "index_head_dim": 64, "index_rope_dim": 32, "index_topk": 2048,
        "num_experts": 128, "experts_per_token": 8, "expert_ffn": 768,
        "rope_theta": 1e7, "eps": 1e-6, "max_len": 65536}


def order_rules(mix: dict, seconds: float, vocab: int, max_len: int):
    """PERF.md section 4's rule (the three longest decodes all due in the
    first half of the window), section 7 (9)'s (none of the ten longest
    decodes among the last tenth of the arrivals) and section 7 (n)'s (none
    of the four longest prompts among the last tenth either), as a function
    of an ``order_seed``."""
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


def test_the_cell_asks_for_its_traffic():
    from benchmarks.lib import traffic

    seconds = float(BENCH["run_seconds"])
    cell = cell_file()
    mix = cell["traffic"]
    assert mix["prompt_tokens"] == {"median": 24576, "sigma": 0.5,
                                    "lo": 8192, "hi": 57344}
    assert mix["new_tokens"] == {"median": 512, "sigma": 0.6, "lo": 128,
                                 "hi": 2048}
    assert (cell["job"]["slots"], cell["job"]["max_len"]) == (8, 65536)
    assert {cell["job"][k] for k in ("dtype", "param_dtype", "kv_dtype")} \
        == {"bfloat16"}
    entry = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["chips"]) == (NAME, 1)
    trace = traffic.request_trace(1, mix, seconds, 151936, 65536)
    buckets = {max(8, 1 << (len(r["prompt"]) - 1).bit_length())
               for r in trace}
    warmed = {max(8, 1 << (n - 1).bit_length())
              for n in cell["job"]["warm_prompt_lens"]}
    assert buckets == warmed and 65536 in buckets       # each is warmed
    assert max(r["max_new_tokens"] for r in trace) <= cell["check"]["pad_new"]
    assert max(len(r["prompt"]) + r["max_new_tokens"] for r in trace) <= 65536
    meets = order_rules(mix, seconds, 151936, 65536)
    chosen = mix["order_seed"]
    assert meets(chosen)
    assert not any(meets(s) for s in range(chosen))


def test_the_cell_reports_the_shares_of_the_peak_and_the_new_metrics():
    mine = {m["name"] for m in BENCH["per_layer"] if CELL in cells_of(m)}
    new = {"kernel.dsa_prefill_roofline", "kernel.dsa_decode_roofline",
           "model.dsa_share", "kvcache.dsa_decode_step_mbu",
           "kvcache.dsa_attended_share_p50"}
    assert new | {"model.serve_mfu", "device.idle_share.serve",
                  "scheduler.queue_wait_p90_ms",
                  "scheduler.queue_wait_in_prefill_share",
                  "scheduler.batch_occupancy_p50",
                  "kvcache.prefill_ms_per_tok", "kvcache.prefill_pad_share",
                  "kvcache.prefill_device_share",
                  "kvcache.step_dispatch_ms_p50", "moe.experts_touched_p50",
                  "moe.expert_load_max_p95", "model.expert_ffn_share"} == mine
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for name in new:
        assert by_name[name]["workloads"] == [CELL]
        params = json.loads((ROOT / "benchmarks/metrics" / f"{name}.json")
                            .read_text())
        assert params["reader"]
    e2e = {m["name"] for m in BENCH["end_to_end"] if CELL in cells_of(m)}
    assert e2e == {"serve_tok_s", "ttft_p90_ms", "setup_s"}


def test_costs_count_the_kernels_work_once():
    from benchmarks.lib import keye_costs as costs

    c = costs.prefill_call(CONFIG, 8192)
    scored = 8192 * 8193 / 2
    attended = 2048 * 2049 / 2 + (8192 - 2048) * 2048
    assert c["flops"] == scored * 2 * 16 * 64 + attended * 4 * 4096
    assert c["bytes"] == 8192 * (2 * (2 * 4096 + 2 * 512 + 1024 + 64) + 64)
    s = costs.step_kernels(CONFIG, 4 * 8 * 30000, 4 * 8 * 2048, 8)
    assert s["bytes"] > 4 * 8 * 30000 * 128
    r = costs.decode_round_bytes(CONFIG, 50.0, 4 * 8 * 30000, 4 * 8 * 2048)
    assert r["kv_rows"] == 4 * 8 * 2048 * 2 * 512 * 2
    assert r["experts"] == 4 * 50 * 3 * 2048 * 768 * 2
    # a request's FLOPs: every layer's products a token, keys scored and
    # attended, the head once a generated token
    assert costs.serve_flops(CONFIG, 4, 1) > 4 * costs.token_flops(CONFIG)


def test_the_tiny_cell_runs_through_the_new_driver():
    result = run_tiny("tiny-serve-keye", CELL)
    assert result["correct"], result["checks"]
    assert result["checks"]["selection_gap"]["value"] == 0.0
    assert result["checks"]["attend_gap"]["value"] < 1e-5
    assert set(result["checks"]) == {*CHECKS, "requests_failed",
                                     "compiles_in_window"}


@pytest.mark.parametrize("fault", [
    "prefill_dense", "prefill_last_rows", "step_dense", "step_last_rows",
    "stale_rows"])
def test_a_fault_planted_in_the_program_fails_a_limit(fault):
    """The tiny cell served by a program with the fault in it: what the
    served attention left in the table, or the logits, fail a limit."""
    from benchmarks.drivers import serve_keye

    assert fault in serve_keye.PROGRAM_FAULTS
    with serve_keye.planted(fault):
        result = run_tiny("tiny-serve-keye", CELL, seed=3900000011)
    checks = result["checks"]
    assert not result["correct"]
    assert any(checks[k]["value"] > checks[k]["limit"] for k in CHECKS), \
        checks


@pytest.fixture(scope="module")
def served():
    """One tiny window served, its sample drawn and the table freed, as a
    check or a calibration leaves it."""
    import jax

    from benchmarks.drivers import serve_keye
    from helpers import tiny

    bench, cell, config = tiny("tiny-serve-keye", CELL)
    run = serve_keye.Run(cell, config, seed=3900000011, seconds=1.0,
                         devices=jax.devices()[:1], note=lambda *_: None)
    run.setup()
    run.window()
    sample = run.sample()
    run.free()
    return run, sample


def test_the_sample_holds_the_longest_and_the_probe(served):
    run, sample = served
    total = {req["rid"]: len(req["prompt"]) + len(t)
             for req, t in run.finished}
    rids = [req["rid"] for req, _ in sample]
    assert total[rids[0]] == max(total.values())
    assert run.probe is not None and run.probe["rid"] in rids
    assert run.probe["stale"] > 0       # a longer occupant's rows remain
    assert len(run.probe["layers"]) == 2
    n = run.probe["layers"][0]["keys"].shape[0]
    prompt = next(len(r["prompt"]) for r, _ in run.finished
                  if r["rid"] == run.probe["rid"])
    assert run.probe["at"].tolist() == [n - 1, prompt - 1]


@pytest.mark.parametrize("variant", [{"mode": "fp8"}] + [
    {"fault": f} for f in FAULTS])
def test_the_control_and_the_planted_faults_fail_a_limit(served, variant):
    run, sample = served
    limits = run.cell["limits"]
    program = run.gaps(sample)
    assert all(program[k] <= limits[k] for k in CHECKS)
    got = run.gaps(sample, **variant)
    assert any(got[k] > limits[k] for k in CHECKS), got


def test_the_new_readers_read_the_windows_records_and_the_kernels_events(
        monkeypatch):
    from types import SimpleNamespace

    from benchmarks.lib import keye_costs, keye_readers, peaks, program_spans
    from benchmarks.lib.xplane import Event

    records = [{"name": "decode_step", "start": float(i), "end": i + 0.01,
                "attrs": {"active": 8, "experts_touched": 50.0,
                          "index_rows": 4 * 8 * 20000,
                          "attended_rows": 4 * 8 * 2048}}
               for i in range(5)]
    monkeypatch.setattr(program_spans, "window", lambda params: records)
    ctx = {"config": CONFIG, "cell": cell_file(), "peaks":
           peaks.lookup("TPU v5e"), "trace": None}
    params = json.loads((ROOT / "benchmarks/metrics/"
                         "kvcache.dsa_decode_step_mbu.json").read_text())
    need = sum(keye_costs.decode_round_bytes(
        CONFIG, 50.0, 4 * 8 * 20000, 4 * 8 * 2048).values())
    assert keye_readers.decode_step_mbu(params, {}, ctx) == pytest.approx(
        100 * need / 0.01 / 819e9)
    share = json.loads((ROOT / "benchmarks/metrics/"
                        "kvcache.dsa_attended_share_p50.json").read_text())
    assert program_spans.attr_ratio_percentile(share, {}, ctx) \
        == pytest.approx(100 * 2048 / 20000)
    # the kernels' events, named by their whole instruction
    ops = [Event("%dsa_prefill.3 = bf16[32,8192,128]{2,1,0} custom-call()",
                 0.0, 5e7),
           Event("%dsa_step_index = f32[8,1,65536]{2,1,0} custom-call()",
                 6e7, 1e5),
           *[Event(f"%dsa_step_attend.{i} = bf16[8,32,128]{{2,1,0}} "
                   f"custom-call()", 7e7 + i, 1e5) for i in range(4)]]
    ctx["trace"] = SimpleNamespace(ops={"/device:TPU:0": ops})
    pre = json.loads((ROOT / "benchmarks/metrics/"
                      "kernel.dsa_prefill_roofline.json").read_text())
    got = keye_readers.prefill_roofline(pre, {}, ctx)
    cost = keye_costs.prefill_call(CONFIG, 8192)
    assert got == pytest.approx(100 * max(cost["flops"] / 197e12,
                                          cost["bytes"] / 819e9) / 0.05)
    assert 0 < got < 100
    step = json.loads((ROOT / "benchmarks/metrics/"
                       "kernel.dsa_decode_roofline.json").read_text())
    got = keye_readers.step_roofline(step, {}, ctx)
    cost = keye_costs.step_kernels(CONFIG, 4 * 8 * 20000, 4 * 8 * 2048, 8)
    assert got == pytest.approx(100 * max(cost["flops"] / 197e12,
                                          cost["bytes"] / 819e9) / 5e-4)
    # an older program: nothing to read
    monkeypatch.setattr(program_spans, "window", lambda params: [])
    ctx["trace"] = SimpleNamespace(ops={"/device:TPU:0": []})
    for params, reader in ((pre, keye_readers.prefill_roofline),
                           (step, keye_readers.step_roofline),
                           (params, keye_readers.decode_step_mbu)):
        assert reader(params, {}, ctx) is None


def test_the_calibration_serves_the_program_the_control_and_every_fault():
    """``calibrate_keye.readings`` on the tiny cell: the program within
    every limit, the control and each fault (in the reference and in the
    program) past one."""
    import jax

    from benchmarks import calibrate_keye
    from benchmarks.drivers import serve_keye
    from benchmarks.lib import keye_reference
    from helpers import tiny

    bench, cell, config = tiny("tiny-serve-keye", CELL)
    got = []
    calibrate_keye.readings(
        cell, config, [3900000011], jax.devices()[:1], controls=1,
        faults=list(serve_keye.PROGRAM_FAULTS),
        reference_faults=list(keye_reference.FAULTS), new=6, seconds=1.0,
        emit=got.append, note=lambda *_: None)
    limits = cell["limits"]
    by_who = {r["who"]: r for r in got}
    assert set(by_who) == {"program", "control_fp8"} | {
        f"reference_{f}" for f in keye_reference.FAULTS} | {
        f"program_{f}" for f in serve_keye.PROGRAM_FAULTS}
    assert all(by_who["program"][k] <= limits[k] for k in CHECKS)
    assert by_who["program"]["probe_stale"] > 0
    for who, r in by_who.items():
        if who != "program":
            assert any(r[k] > limits[k] for k in CHECKS), (who, r)

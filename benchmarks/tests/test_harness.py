"""The harness is driven by data: every file is found by name and agrees
with BENCHMARK.json, a four-chip copy of a cell runs through the same
driver, and a run with the timed path broken comes out not correct."""

from __future__ import annotations

import json
import re
import subprocess
import sys

import pytest

from helpers import ROOT, run_tiny

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]
TRAIN, SERVE = "train-gpt2m-1k", "serve-gpt2l-chat"


def cells_of(metric: dict) -> list[str]:
    return metric.get("workloads", [w["name"] for w in BENCH["workloads"]])


def test_every_file_is_found_by_name():
    for conf in BENCH["configs"]:
        assert (ROOT / conf["file"]).is_file()
        assert conf["file"] == f"benchmarks/configs/{conf['name']}.json"
    for w in BENCH["workloads"]:
        cell = json.loads(
            (ROOT / "benchmarks/workloads" / f"{w['name']}.json").read_text())
        assert cell["config"] == w["config"]
        assert (ROOT / "benchmarks/drivers" / f"{cell['driver']}.py").is_file()
    on_disk = {p.stem for p in (ROOT / "benchmarks/metrics").glob("*.json")}
    assert on_disk == {m["name"] for m in METRICS}


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_file_names_a_reader_and_copies_nothing(metric):
    """BENCHMARK.json alone owns units, layers and lists of cells, so a
    new cell joins a metric without an edit under ``benchmarks/``."""
    from benchmarks import run as runmod

    params = json.loads((ROOT / "benchmarks/metrics"
                         / f"{metric['name']}.json").read_text())
    assert not set(params) & set(metric)
    assert callable(runmod.resolve(params["reader"]))


def test_names_and_units_use_the_allowed_characters():
    names = [m["name"] for m in METRICS]
    names += [w["name"] for w in BENCH["workloads"]]
    names += [w["traffic"] for w in BENCH["workloads"]]
    names += [c["name"] for c in BENCH["configs"]]
    names += [k for c in BENCH["configs"] for k in c["reduced"]]
    assert all(NAME.match(n) for n in names), names
    assert len(set(m["name"] for m in METRICS)) == len(METRICS)
    assert all(UNIT.match(m["unit"]) for m in METRICS)
    assert all(m["better"] in ("lower", "higher") for m in METRICS)
    for text in [w["why"] for w in BENCH["workloads"]] + [
            c["why"] for c in BENCH["configs"]] + [
            m["layer"] for m in BENCH["per_layer"]]:
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_every_layer_metric_moves_a_metric_its_cells_report():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        assert set(cells_of(m)) <= set(cells_of(e2e[m["moves"]])), m["name"]
    for w in BENCH["workloads"]:
        mine = [m for m in BENCH["end_to_end"] if w["name"] in cells_of(m)]
        assert {"setup_s"} < {m["name"] for m in mine}
        assert any(w["name"] in cells_of(m) for m in BENCH["per_layer"])


def test_mfu_stands_beside_the_rooflines():
    for m in BENCH["per_layer"]:
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
            assert any("mfu" in re.split(r"[._]", o["name"])
                       and o["moves"] == m["moves"]
                       and set(cells_of(m)) <= set(cells_of(o))
                       for o in BENCH["per_layer"])


def test_the_code_names_no_cell_config_or_metric():
    taboo = [m["name"] for m in METRICS if m["name"] != "setup_s"]
    taboo += [w["name"] for w in BENCH["workloads"]]
    taboo += [c["name"] for c in BENCH["configs"]]
    code = [ROOT / "benchmarks/run.py",
            *(ROOT / "benchmarks/drivers").glob("*.py"),
            *(ROOT / "benchmarks/lib").glob("*.py")]
    for path in code:
        text = path.read_text()
        assert not [t for t in taboo if t in text], path


def test_peaks_raise_on_an_unknown_device_kind():
    from benchmarks.lib import peaks

    assert peaks.lookup("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="not in the peak table"):
        peaks.lookup("cpu")


def test_the_command_refuses_a_cpu_backend():
    out = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", TRAIN,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
             "HOME": str(ROOT / ".bench_home")})
    assert out.returncode != 0 and out.stdout == ""
    assert "nothing was run" in out.stderr


def check_metrics(result: dict, cell: str, trace: bool = False):
    want = {m["name"] for m in BENCH["per_layer" if trace else "end_to_end"]
            if cell in cells_of(m)}
    assert set(result["metrics"]) == want
    assert list(result)[-1] == "checks"


@pytest.mark.parametrize("chips", [1, 4])
def test_train_cell_runs_through_the_driver(chips):
    result = run_tiny("tiny-train", TRAIN, chips=chips)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["device"]["count"] == chips
    check_metrics(result, TRAIN)


@pytest.mark.parametrize("chips", [1, 4])
def test_serve_cell_runs_through_the_driver(chips):
    result = run_tiny("tiny-serve", SERVE, chips=chips)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 4
    check_metrics(result, SERVE)


def test_the_serve_slice_follows_the_longest_mid_window_prompt():
    """On the real cell's trace: the traced slice opens just before a long
    prompt is due, so it holds a prefill; and the order of the trace is
    the one its rule picks."""
    from benchmarks import run as runmod
    from benchmarks.drivers import serve
    from benchmarks.lib import traffic

    _, cell, config = runmod.load_cell(SERVE)
    seconds = float(BENCH["run_seconds"])
    run = serve.Run(cell, config, seed=5, seconds=seconds, devices=[None])
    run.trace = traffic.request_trace(5, run.mix, seconds, run.vocab,
                                      run.max_len)
    after, length = run.trace_slice()
    due = {r["arrival_s"]: len(r["prompt"]) for r in run.trace}
    at = min(due, key=lambda t: abs(t - after - 0.1))
    assert abs(at - after - 0.1) < 1e-9 and seconds / 4 <= at <= 3 * seconds / 4
    assert due[at] == max(n for t, n in due.items()
                          if seconds / 4 <= t <= 3 * seconds / 4) >= 256
    assert length == cell["job"]["trace_seconds"]

    def long_decodes_early(order_seed):
        trace = traffic.request_trace(
            5, {**run.mix, "order_seed": order_seed}, seconds, run.vocab,
            run.max_len)
        top = sorted(trace, key=lambda r: -r["max_new_tokens"])[:3]
        return all(r["arrival_s"] < seconds / 2 for r in top)

    chosen = run.mix["order_seed"]
    assert long_decodes_early(chosen)
    assert not any(long_decodes_early(s) for s in range(chosen))


# ---- the timed path broken underneath: `correct` has to come out false

def test_a_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch):
    from distributed_tensorflow_tpu.engines.base import Engine

    real = Engine.many_step

    def unchanged(self, state, xs, ys):
        _, metrics = real(self, state, xs, ys)
        return self.__dict__.setdefault("_kept", state), metrics

    # the engine donates its state, so the kept copy must outlive the call
    monkeypatch.setattr(Engine, "build_many_step", _undonated(Engine))
    monkeypatch.setattr(Engine, "many_step", unchanged)
    result = run_tiny("tiny-train", TRAIN)
    assert not result["correct"]
    assert result["checks"]["update_norm_gap"]["value"] > 0.9


def _undonated(engine_cls):
    import jax

    def build(self, k):
        step = self._base_step()

        def many(state, xs_k, ys_k):
            import jax.numpy as jnp

            return jax.lax.scan(lambda st, b: step(st, *b), state,
                                (jnp.stack(xs_k), jnp.stack(ys_k)))

        return jax.jit(many)

    return build


def test_half_of_the_batch_left_out_is_not_correct(monkeypatch):
    import numpy as np

    from distributed_tensorflow_tpu.engines.base import Engine

    real = Engine.shard_batch

    def half(self, x, y, *a, **k):
        n = len(x) // 2      # the mean is taken over the first half alone
        return real(self, np.concatenate([x[:n], x[:n]]),
                    np.concatenate([y[:n], y[:n]]), *a, **k)

    monkeypatch.setattr(Engine, "shard_batch", half)
    result = run_tiny("tiny-train", TRAIN)
    assert not result["correct"], result["checks"]


def test_a_chunk_that_feeds_its_first_batch_to_every_step_is_not_correct(
        monkeypatch):
    """Exact at a chunk of one step; only the window's own k-step program
    shows it."""
    from distributed_tensorflow_tpu.engines.base import Engine

    real = Engine.many_step
    monkeypatch.setattr(
        Engine, "many_step", lambda self, state, xs, ys: real(
            self, state, [xs[0]] * len(xs), [ys[0]] * len(ys)))
    result = run_tiny("tiny-train", TRAIN)
    assert not result["correct"], result["checks"]
    assert result["checks"]["loss_step2_rel"]["value"] > \
        result["checks"]["loss_step2_rel"]["limit"]


def test_four_chips_that_exchange_nothing_new_are_not_correct(monkeypatch):
    """Four chips that all step on the first chip's rows: the mean over
    chips then carries nothing of the other three quarters, which is what
    an exchange left out amounts to.  (The sync engine's all-reduce is the
    implicit psum of the AD transpose under ``shard_map``; with it taken
    out the step does not type-check, so it cannot be planted by name.  A
    four-chip cell's PR plants it on its own path.)"""
    import numpy as np

    from distributed_tensorflow_tpu.engines.base import Engine

    real = Engine.shard_batch

    def one_quarter(self, x, y, *a, **k):
        n = len(x) // 4
        return real(self, np.concatenate([x[:n]] * 4),
                    np.concatenate([y[:n]] * 4), *a, **k)

    monkeypatch.setattr(Engine, "shard_batch", one_quarter)
    result = run_tiny("tiny-train", TRAIN, chips=4)
    assert not result["correct"], result["checks"]
    assert result["checks"]["moment_norm_gap"]["value"] > \
        result["checks"]["moment_norm_gap"]["limit"]


def test_a_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    from distributed_tensorflow_tpu.serving.kv_cache import SlotKVCache

    real = SlotKVCache.advance

    def altered(self, *a, **k):
        out = real(self, *a, **k).copy()
        out[0] = (out[0] + 1) % 200
        return out

    monkeypatch.setattr(SlotKVCache, "advance", altered)
    result = run_tiny("tiny-serve", SERVE)
    assert not result["correct"], result["checks"]
    assert result["checks"]["token_logit_gap"]["value"] > \
        result["checks"]["token_logit_gap"]["limit"]

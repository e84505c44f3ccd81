"""CLI integration tests: the full reference-compatible flag surface driving
real training on the fake mesh (the analogue of the reference's only
"test" — an end-to-end run, SURVEY.md §4)."""

import json
import re
import shlex
from pathlib import Path

import pytest

from distributed_tensorflow_tpu.cli import build_parser, main, select_engine, str2bool


def _readme_commands():
    """Every CLI command of README.md's fenced blocks, as argv: the lines
    that start ``python -m distributed_tensorflow_tpu.cli`` or ``python
    initializer.py`` (which hands its argv to the same parser), with
    continuation lines joined, comments and redirections dropped, and the
    ``...`` that stands for "the training flags" left out."""
    text = (Path(__file__).resolve().parents[1] / "README.md").read_text()
    found = []
    for block in re.findall(r"```bash\n(.*?)```", text, re.S):
        for line in block.replace("\\\n", " ").splitlines():
            words = shlex.split(line, comments=True)
            if words[:3] == ["python", "-m", "distributed_tensorflow_tpu.cli"]:
                argv = words[3:]
            elif words[:2] == ["python", "initializer.py"]:
                argv = words[2:]
            else:
                continue
            if ">" in argv:
                argv = argv[:argv.index(">")]
            found.append([w for w in argv if w != "..."])
    return found


@pytest.mark.parametrize("argv", _readme_commands(), ids=" ".join)
def test_readme_command_is_accepted_by_the_parser(argv):
    """Nothing is run: a flag the README names and the parser has lost (or
    a value it no longer takes) exits here with argparse's message."""
    build_parser().parse_args(argv)


def test_str2bool_parity():
    # reference initializer.py:59-67
    for v in ("yes", "true", "t", "y", "1"):
        assert str2bool(v) is True
    for v in ("no", "false", "f", "n", "0"):
        assert str2bool(v) is False
    with pytest.raises(Exception):
        str2bool("maybe")


@pytest.mark.parametrize("argv,engine", [
    (["-m", "c", "-cs", "sync"], "sync"),
    (["-m", "centralized", "-cs", "async"], "async"),
    (["-m", "d", "-ds", "keras"], "allreduce"),
    (["-m", "d", "-ds", "graph"], "gossip"),
    (["-m", "decentralized", "-ds", "custom"], "gossip"),
    (["-m", "tpu_pod"], "sync"),
    (["-m", "t"], "sync"),
])
def test_mode_dispatch(argv, engine):
    args = build_parser().parse_args(argv)
    assert select_engine(args) == engine


def test_reference_flag_surface_accepted():
    # every reference flag parses (reference initializer.py:72-114)
    args = build_parser().parse_args(
        ["-m", "c", "-cs", "sync", "-ds", "keras", "-n", "4", "-b", "32",
         "-ti", "0", "-ca", "y"])
    assert args.number_nodes == 4 and args.batch_size == 32
    assert args.cpu_affinity is True


@pytest.mark.parametrize("argv", [
    ["-m", "tpu_pod", "-n", "8", "-b", "8"],
    ["-m", "c", "-cs", "async", "-n", "8", "-b", "8", "--sync-every", "4"],
    ["-m", "d", "-ds", "custom", "-n", "8", "-b", "8", "-d", "2"],
])
@pytest.mark.slow
def test_cli_end_to_end(tmp_path, capsys, argv):
    out = tmp_path / "events.jsonl"
    summary = main(argv + ["--dataset", "synthetic", "--model", "mlp",
                           "--result-path", str(out), "--log-every", "0",
                           "-e", "1"])
    assert summary["n_devices"] == 8
    assert summary["steps"] > 0
    assert 0.0 <= summary["test_accuracy"] <= 1.0
    # stdout carries the one-line JSON summary
    printed = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert printed["steps"] == summary["steps"]
    # JSONL sink got the reference event triple + summary
    events = [json.loads(l)["event"] for l in out.read_text().splitlines()]
    assert events[:2] == ["start", "done"]
    assert "results" in events and "summary" in events


def test_cli_supervisor_channel():
    """--supervisor wires a CLI run to an external reference-style harness:
    the listener must observe exactly the reference event triple
    ['start', ['done', elapsed], ['results', accuracy]]
    (reference server.py:121-124, 182-187; VERDICT r1 missing #1)."""
    from distributed_tensorflow_tpu.utils.supervisor import SupervisorListener

    listener = SupervisorListener()
    summary = main(["-m", "tpu_pod", "-n", "8", "-b", "8",
                    "--dataset", "synthetic", "--model", "mlp",
                    "--log-every", "0", "-e", "1",
                    "--supervisor", f"127.0.0.1:{listener.port}"])
    listener.close()  # joins the serve thread (sink closed inside main)
    assert listener.messages[0] == "start"
    done = listener.messages[1]
    assert done[0] == "done" and done[1] == pytest.approx(
        summary["elapsed_s"], rel=1e-6)
    assert listener.messages[2] == ["results", summary["test_accuracy"]]


def test_tt_and_sa_must_come_together():
    """'-tt worker' without '-sa' must error, not silently run single-process
    (unlike the reference's role dispatch on task_type alone)."""
    with pytest.raises(SystemExit):
        main(["-tt", "worker"])
    with pytest.raises(SystemExit):
        main(["-sa", "127.0.0.1:9999"])


def test_dtype_handling_for_plugin_and_registered_models():
    import flax.linen as nn

    from distributed_tensorflow_tpu import models as modellib
    from distributed_tensorflow_tpu.utils.harness import (
        ExperimentConfig, _resolve_model)

    class NoDtype(nn.Module):
        num_classes: int = 10

        @nn.compact
        def __call__(self, x, train=False):
            return nn.Dense(self.num_classes)(x.reshape((x.shape[0], -1)))

    @modellib.register("nodtype_test_mlp")
    def _factory(num_classes=10, **kw):
        return NoDtype(num_classes=num_classes, **kw)

    # registered model lacking a dtype field works at the f32 default ...
    m = _resolve_model(ExperimentConfig(model="nodtype_test_mlp"), 10)
    assert isinstance(m, NoDtype)
    # ... and fails loudly (not TypeError) when bf16 is requested
    with pytest.raises(ValueError, match="dtype"):
        _resolve_model(
            ExperimentConfig(model="nodtype_test_mlp", dtype="bf16"), 10)
    # plug-in model_fn owns its dtype: --dtype warns instead of silently
    # doing nothing
    with pytest.warns(UserWarning, match="dtype"):
        m = _resolve_model(
            ExperimentConfig(model_fn=lambda: NoDtype(), dtype="bf16"), 10)
    assert isinstance(m, NoDtype)


def test_steps_to_accuracy_step_granularity():
    from distributed_tensorflow_tpu.utils.harness import ExperimentConfig, steps_to_accuracy

    cfg = ExperimentConfig(engine="sync", model="mlp", dataset="synthetic",
                           n_devices=8, batch_size=16, learning_rate=5e-3)
    r = steps_to_accuracy(cfg, target=0.9, max_steps=300, eval_every=8)
    assert r["reached"], r
    assert r["steps"] % 8 == 0  # eval cadence honored
    assert r["steps"] < 300
    # resolution is MEASURED (gap between the crossing eval and the one
    # before), labeled synthetic, and routed through the one Trainer loop
    assert r["step_resolution"] <= 8
    assert r["synthetic"] is True


def test_steps_to_accuracy_max_steps_final_eval():
    """Hitting max_steps must still report a real (final-step) accuracy,
    never a stale or never-computed one (review r3 finding)."""
    from distributed_tensorflow_tpu.utils.harness import ExperimentConfig, steps_to_accuracy

    cfg = ExperimentConfig(engine="sync", model="mlp", dataset="synthetic",
                           n_devices=8, batch_size=16)
    r = steps_to_accuracy(cfg, target=1.01, max_steps=7, eval_every=50)
    assert not r["reached"]
    assert r["steps"] == 7
    assert r["accuracy"] > 0.0  # the cap-step eval ran


def test_cli_user_plugin_model_and_dataset_fn():
    """The reference's 'edit model_fn/dataset_fn in initializer.py' contract
    (reference README.md:12): plug-ins override --model/--dataset."""
    from distributed_tensorflow_tpu.data import make_dataset_fn
    from distributed_tensorflow_tpu.models.mlp import MLP

    built = {}

    def model_fn():
        built["model"] = True
        return MLP(num_classes=10, hidden=16)

    summary = main(
        ["-m", "tpu_pod", "-n", "8", "-b", "8", "--log-every", "0",
         "--model", "ignored_because_plugin", "--dataset", "synthetic"],
        model_fn=model_fn, dataset_fn=make_dataset_fn("synthetic"))
    assert built.get("model")
    assert summary["steps"] > 0
    assert summary["test_accuracy"] > 0.5


@pytest.mark.slow
def test_model_arg_passthrough():
    """--model-arg KEY=VALUE reaches the model constructor (a 3-layer
    hidden-48 GPT has a distinct param tree)."""
    import math

    from distributed_tensorflow_tpu.cli import main, parse_model_args
    from distributed_tensorflow_tpu.data.loaders import load_lm_dataset

    assert parse_model_args(["hidden=48", "tie_embeddings=false",
                             "positional=rope"]) == {
        "hidden": 48, "tie_embeddings": False, "positional": "rope"}
    import pytest as _pytest
    with _pytest.raises(Exception, match="KEY=VALUE"):
        parse_model_args(["hidden"])

    def lm_fn(batch_size, type="train", **kw):
        return load_lm_dataset(seq_len=16, vocab_size=64, n_train=64,
                               n_test=32, split=type)

    summary = main(["-m", "t", "-n", "8", "-b", "4", "--model", "gpt",
                    "--dataset", "lm_synth", "--model-arg", "hidden=48",
                    "--model-arg", "layers=1", "--log-every", "0"],
                   dataset_fn=lm_fn)
    assert math.isfinite(summary["test_loss"])


def test_model_arg_typo_fails_loudly():
    """A typo'd --model-arg key must error, not silently train the
    default-size model (the dtype-probe fallback once dropped all kwargs)."""
    from distributed_tensorflow_tpu.utils.harness import (
        ExperimentConfig, run)

    with pytest.raises(TypeError):
        run(ExperimentConfig(engine="sync", model="gpt", dataset="lm_synth",
                             n_devices=8, model_args={"hiden": 256}))


def test_model_arg_rejected_under_pipeline():
    from distributed_tensorflow_tpu.utils.harness import (
        ExperimentConfig, run)

    with pytest.raises(ValueError, match="pipeline-hidden"):
        run(ExperimentConfig(engine="sync", model="gpt", dataset="lm_synth",
                             n_devices=8, pipeline_parallel=2,
                             model_args={"hidden": 64}))


def test_model_arg_reserved_key_rejected_cleanly():
    """--model-arg keys owned by dedicated flags (num_experts under EP,
    dtype anywhere) must raise the clean reserved-key ValueError, not a raw
    'got multiple values' TypeError (ADVICE r3)."""
    from distributed_tensorflow_tpu.utils.harness import (
        ExperimentConfig, run)

    with pytest.raises(ValueError, match="reserved"):
        run(ExperimentConfig(engine="sync", model="moe",
                             dataset="synthetic", n_devices=8,
                             expert_parallel=4, num_experts=4,
                             model_args={"num_experts": 8}))
    with pytest.raises(ValueError, match="reserved"):
        run(ExperimentConfig(engine="sync", model="gpt", dataset="lm_synth",
                             n_devices=8, model_args={"dtype": "float16"}))
    with pytest.raises(ValueError, match="reserved"):
        run(ExperimentConfig(engine="sync", model="gpt",
                             dataset="lm_synth", n_devices=8,
                             seq_parallel=2,
                             model_args={"attention_impl": "ulysses"}))


def test_chip_smoke_refuses_the_cpu(tmp_path):
    """chip_smoke.py is the proof that the system starts on the chip: held
    to the CPU it exits non-zero, says which platform it found, prints no
    result, and does so before anything compiles (a cache placed by the
    environment, with the persistence gates dropped, stays empty)."""
    import os
    import subprocess
    import sys
    from pathlib import Path

    repo = Path(__file__).resolve().parents[1]
    cache = tmp_path / "cache"
    cache.mkdir()
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_LOG_COMPILES="1",
               JAX_COMPILATION_CACHE_DIR=str(cache),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="-1")
    proc = subprocess.run([sys.executable, str(repo / "chip_smoke.py")],
                          cwd=str(tmp_path), env=env, capture_output=True,
                          text=True, timeout=120)
    assert proc.returncode not in (0, None)
    assert "'cpu'" in proc.stderr and "no TPU" in proc.stderr
    assert proc.stdout.strip() == ""
    assert "Compiling" not in proc.stderr
    assert list(cache.iterdir()) == []


def test_chip_smoke_verdict_line_has_exactly_the_contract_keys():
    """The last line chip_smoke.py prints is read by the driver as a JSON
    object with exactly ``ok`` and ``device`` = {platform, kind, count};
    phase detail belongs on the report line before it."""
    import importlib.util
    import json
    from pathlib import Path

    path = Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    chip_smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(chip_smoke)

    device = chip_smoke.device_facts()
    line = chip_smoke.verdict_line(True, {**device, "extra": 1})
    assert "\n" not in line
    verdict = json.loads(line)
    assert set(verdict) == {"ok", "device"} and verdict["ok"] is True
    assert set(verdict["device"]) == {"platform", "kind", "count"}
    assert verdict["device"] == device
    assert isinstance(verdict["device"]["platform"], str)
    assert isinstance(verdict["device"]["kind"], str)
    assert type(verdict["device"]["count"]) is int
    assert json.loads(chip_smoke.verdict_line(False, device))["ok"] is False


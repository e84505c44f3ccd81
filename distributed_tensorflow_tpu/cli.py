"""L4 CLI/launcher — flag-compatible with the reference's initializer.py.

Reference surface (reference initializer.py:72-114):
  -m/--mode {c,centralized,d,decentralized}   -cs {sync,async}
  -ds {keras,graph,custom}   -n N   -b B   -tt {server,worker}   -ti I
  -sa ADDR   -ca {y,n}

Mapping to TPU-native engines (no processes are spawned — one SPMD program
owns all local devices; compare reference initializer.py:134-145 which forks
N+1 processes):

  -m c  -cs sync    → sync engine      (parameter-server sync semantics)
  -m c  -cs async   → async engine     (local SGD, periodic averaging)
  -m d  -ds keras   → allreduce engine (RING-allreduce semantics)
  -m d  -ds graph   → gossip engine    (implemented — ref raises
  -m d  -ds custom  → gossip engine     NotImplementedError, init.py:175-181)
  -m d  -ds fsdp    → fsdp engine      (ZeRO sharded params+optimizer — the
                                        ref's single-home optimizer,
                                        server.py:52-55, TPU-first)
  -m t/tpu_pod      → sync engine      (BASELINE.md north-star mode)

``-n`` selects TPU device count (BASELINE.md: "-n maps to device count");
``-b`` stays the per-worker batch, so the global batch is b×n like the
reference's aggregate.  ``-ca`` is accepted-and-ignored: core pinning
simulated "1 node = 1 core" (reference server.py:144-146), and a TPU device
*is* the node here.  ``-tt/-ti/-sa`` become `jax.distributed.initialize`
coordinates for real multi-host pods.
"""

from __future__ import annotations

import argparse
import json
import sys

from distributed_tensorflow_tpu.utils.harness import (
    ExperimentConfig, resolve_compile_cache, run)


def parse_model_args(pairs: list[str]) -> dict:
    """KEY=VALUE list → kwargs dict with literal-ish value parsing."""
    out = {}
    for pair in pairs:
        if "=" not in pair:
            raise argparse.ArgumentTypeError(
                f"--model-arg expects KEY=VALUE, got '{pair}'")
        k, v = pair.split("=", 1)
        if v.lower() in ("true", "false"):
            out[k] = v.lower() == "true"
        else:
            for cast in (int, float):
                try:
                    out[k] = cast(v)
                    break
                except ValueError:
                    continue
            else:
                out[k] = v
    return out


def str2bool(v: str) -> bool:
    """Parity with reference str2bool (reference initializer.py:59-67)."""
    if isinstance(v, bool):
        return v
    if v.lower() in ("yes", "true", "t", "y", "1"):
        return True
    if v.lower() in ("no", "false", "f", "n", "0"):
        return False
    raise argparse.ArgumentTypeError("Boolean value expected.")


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="distributed_tensorflow_tpu",
        description="TPU-native distributed training (reference-flag compatible)")
    p.add_argument("-m", "--mode", default="tpu_pod",
                   choices=["c", "centralized", "d", "decentralized", "t", "tpu_pod"])
    p.add_argument("-cs", "--centralized_strategy", default="sync",
                   choices=["sync", "async"])
    p.add_argument("-ds", "--decentralized_strategy", default="keras",
                   choices=["keras", "graph", "custom", "sync", "fsdp"])
    p.add_argument("-n", "--number_nodes", type=int, default=None,
                   help="TPU device count (default: all local devices)")
    p.add_argument("-b", "--batch_size", type=int, default=32,
                   help="per-worker batch; global batch = b × n")
    p.add_argument("-tt", "--task_type", default=None, choices=["server", "worker"],
                   help="multi-host role (server == coordinator host)")
    p.add_argument("-ti", "--task_index", type=int, default=0)
    p.add_argument("-sa", "--server_address", default=None,
                   help="coordinator address host:port for multi-host")
    p.add_argument("-ca", "--cpu_affinity", type=str2bool, nargs="?", const=True,
                   default=False, help="accepted for compatibility; no-op on TPU")
    # TPU-native additions
    p.add_argument("--model", default="mlp",
                   help="registered model name "
                        "(mlp|cnn|resnet20|bert_tiny|gpt|moe)")
    p.add_argument("--dataset", default="mnist",
                   help="mnist|fashion_mnist|cifar10|synthetic|glue_synth|"
                        "lm_synth")
    p.add_argument("-e", "--epochs", type=int, default=1,
                   help="reference hardwires 1 (SURVEY.md §2.4(6))")
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--lr-schedule", default="constant",
                   choices=["constant", "cosine", "linear"],
                   help="LR schedule over epochs × steps-per-epoch; combine "
                        "with --warmup-steps for a linear ramp from 0")
    p.add_argument("--warmup-steps", type=int, default=0,
                   help="linear LR warmup steps (0 disables)")
    p.add_argument("--grad-accum", type=int, default=1,
                   help="microbatches accumulated per optimizer step: ~K× "
                        "less activation memory at identical math.  "
                        "Composes with sync/allreduce/fsdp, -tp, fsdp×tp, "
                        "-sp, -ep, and the tp×sp/ep×sp composites; the "
                        "pipeline modes microbatch via --microbatches, and "
                        "the async/gossip engines reject it (their local "
                        "steps already decouple optimizer cadence)")
    p.add_argument("--weight-decay", type=float, default=0.0,
                   help=">0: AdamW decoupled weight decay")
    p.add_argument("--clip-norm", type=float, default=0.0,
                   help=">0: clip gradients to this global norm before the "
                        "update")
    p.add_argument("--sync-every", type=int, default=10,
                   help="async engine: parameter-averaging period")
    p.add_argument("-d", "--degree", type=int, default=1,
                   help="gossip neighbor degree (the reference's commented-out "
                        "-d flag, initializer.py:90-92)")
    p.add_argument("--num-processes", type=int, default=None,
                   help="multi-host: total process count")
    p.add_argument("-sp", "--seq-parallel", type=int, default=1,
                   help="shard sequences over this many devices (long-context "
                        "mode; requires a sequence model, e.g. --model bert_tiny)")
    p.add_argument("--attention", default="ring",
                   choices=["ring", "ring_flash", "ulysses", "ulysses_flash", "flash"],
                   help="attention strategy: ring/ring_flash/ulysses/"
                        "ulysses_flash shard the sequence over -sp devices "
                        "(the *_flash variants run the Pallas flash kernel "
                        "as the local math inside the ring / Ulysses "
                        "communication schedule); flash = single-device "
                        "Pallas kernel, valid only with -sp 1 (sequence "
                        "models)")
    p.add_argument("--positional", default="learned",
                   choices=["learned", "rope"],
                   help="GPT position encoding: learned table | RoPE "
                        "(rotary, no table — q/k rotated by position)")
    p.add_argument("--kv-heads", type=int, default=None,
                   help="GPT grouped-query attention: K/V head count "
                        "(< --heads; 1 = multi-query).  Shrinks the decode "
                        "KV cache by heads/kv_heads")
    p.add_argument("--remat", action="store_true",
                   help="activation checkpointing: store each transformer "
                        "block's input only, recompute the block in "
                        "backward (~K x less activation memory for ~1/3 "
                        "more FLOPs).  The long-context memory lever.  "
                        "Sequence models only; under pipelines it bounds "
                        "the GPipe tick stash but is a documented no-op "
                        "for --pipeline-schedule 1f1b (the 1F1B stash is "
                        "already bounded at S slots)")
    p.add_argument("--sample", type=int, default=0, metavar="N",
                   help="after training a GPT LM, greedy-decode N tokens "
                        "per prompt from the final params (KV-cache "
                        "sampler, multi-device over the run's mesh; under "
                        "--pipeline-parallel a sequential-forward decode "
                        "over the pipe-stacked stages — dense-FFN stages "
                        "only, MoE stages are rejected with the routing-"
                        "capacity reason) and record prompts+continuations "
                        "in the summary")
    p.add_argument("--sample-prompt-len", type=int, default=8,
                   help="prompt tokens taken from the test split per "
                        "sampled row (--sample)")
    p.add_argument("--serve", type=int, default=0, metavar="N",
                   help="after training a GPT LM, run a continuous-"
                        "batching serving window of N requests through "
                        "the slot-based KV cache + in-flight scheduler "
                        "(distributed_tensorflow_tpu/serving/): requests "
                        "queue into --serve-slots slots, finished slots "
                        "are evicted and refilled between decode "
                        "iterations, and the summary/run report gain a "
                        "'serve' section (requests/sec/chip, TTFT/ITL "
                        "p50/p95 — gated by `analyze diff` like the "
                        "training metrics).  Per-request request/prefill "
                        "spans and a decode_step a round ride --trace")
    p.add_argument("--serve-slots", type=int, default=4,
                   help="--serve: KV slot table size (requests decoded "
                        "in flight at once; shards over the 'data' mesh "
                        "axis when divisible)")
    p.add_argument("--serve-max-new", type=int, default=16,
                   help="--serve: tokens generated per request")
    p.add_argument("--serve-prompt-len", type=int, default=8,
                   help="--serve: prompt tokens taken from the test "
                        "split per request")
    p.add_argument("--serve-kv-dtype", default=None,
                   choices=["float32", "f32", "bfloat16", "bf16", "int8"],
                   help="--serve: KV slot-table storage dtype (default: "
                        "the model's dtype).  bfloat16 halves the KV "
                        "memory per slot — double the serving slots per "
                        "chip at equal HBM; greedy tokens stay oracle-"
                        "exact on the shipped models.  int8 halves "
                        "bf16's payload again (int8 K/V + one f32 "
                        "max-abs scale per written vector, dequantized "
                        "on the attention read) — token parity vs the "
                        "bf16 oracle is tolerance-based, not bitwise.  "
                        "The dtype and serve_kv_bytes_per_slot ride the "
                        "serve report section (gated by `analyze diff`)")
    p.add_argument("--serve-draft-config", default=None, metavar="SPEC",
                   help="--serve: speculative decoding — a draft GPT "
                        "proposes --serve-draft-k tokens per live slot, "
                        "the served model verifies all k+1 positions in "
                        "ONE batched step, and greedy acceptance keeps "
                        "the emitted stream BITWISE identical to non-"
                        "speculative decode.  SPEC is 'self' (draft = "
                        "the served model + params; accept rate 1) or "
                        "'hidden=64,layers=1,...' GPT size overrides "
                        "(vocab/max_len inherited, fresh-initialized "
                        "from --seed).  Default off: the pre-round-14 "
                        "programs, byte-identical")
    p.add_argument("--serve-draft-k", type=int, default=4, metavar="K",
                   help="--serve-draft-config: draft tokens proposed per "
                        "verify round (capped per round by slot capacity "
                        "and remaining request budgets).  The serve "
                        "section carries serve_accept_rate + the "
                        "proposed/accepted/rejected ledger")
    p.add_argument("--serve-prefill-chunk", type=int, default=0,
                   metavar="T",
                   help="--serve: chunked prefill token budget (Sarathi-"
                        "Serve): admissions prefill in chunks of ≤T "
                        "tokens, at most one chunk per decode iteration, "
                        "so a long prompt cannot stall live slots for "
                        "more than one chunk per token.  0 (default) = "
                        "monolithic prefill (one block program a "
                        "bucket).  Greedy tokens are identical "
                        "either way; TTFT stays arrival→first-token")
    p.add_argument("--serve-prefix-cache", type=int, default=0,
                   metavar="BLOCKS",
                   help="--serve: prefix-cache pool capacity in KV "
                        "blocks (vLLM-style block-granular reuse).  On "
                        "admission the longest cached block-aligned "
                        "prompt prefix is copied into the slot and "
                        "prefill starts at the first uncached block; "
                        "LRU eviction past the bound.  0 (default) = "
                        "off.  hit/miss/evict accounting + "
                        "serve_prefix_cache_hit_rate ride the serve "
                        "section (gated by `analyze diff`)")
    p.add_argument("--serve-prefix-block", type=int, default=16,
                   metavar="T",
                   help="--serve: tokens per prefix-cache block (reuse "
                        "granularity; only full blocks are pooled)")
    p.add_argument("--serve-shared-prefix", type=int, default=0,
                   metavar="T",
                   help="--serve: prepend a fixed T-token synthetic "
                        "system prompt to every request (the dominant "
                        "real-traffic shape prefix caching exists for); "
                        "deterministic from --seed")
    p.add_argument("--serve-slo-ttft", type=float, default=2.0,
                   metavar="S",
                   help="--serve: TTFT SLO target in seconds — a request "
                        "is goodput only when arrival→first-token (queue "
                        "wait included) meets this AND the ITL target; "
                        "the serve section carries "
                        "serve_goodput_under_slo (gated higher-is-better "
                        "by `analyze diff`)")
    p.add_argument("--serve-slo-itl", type=float, default=0.5,
                   metavar="S",
                   help="--serve: inter-token-latency SLO target in "
                        "seconds, judged at each request's own p99 gap")
    p.add_argument("--serve-queue-cap", type=int, default=0,
                   metavar="N",
                   help="--serve: bounded admission — cap the arrived-"
                        "but-unadmitted backlog at N requests; excess "
                        "sheds with 429 accounting (shed_requests / "
                        "serve_shed_rate + a structured `overload` trace "
                        "event) so overload degrades to bounded queue "
                        "wait instead of unbounded TTFT (0 = admit "
                        "everything)")
    p.add_argument("--serve-replicas", type=int, default=1, metavar="N",
                   help="--serve: run the window through a ReplicaSet "
                        "fleet of N continuous-batching replicas "
                        "(serving/fleet.py), each with its own "
                        "--serve-slots KV table, behind a least-loaded "
                        "router.  A replica failure (crash, watchdog "
                        "stall, detected corruption) requeues its queued "
                        "AND in-flight requests to survivors with "
                        "bounded retry — already-streamed tokens are "
                        "never re-emitted (journal fence; resume "
                        "re-prefills prompt+emitted prefix, greedy-"
                        "exact) and retry TTFT stays charged from the "
                        "original arrival.  The serve section gains "
                        "serve_fleet + serve_failover_recovery_p95_s / "
                        "serve_duplicate_emissions (gated by `analyze "
                        "diff`).  1 (default) = the single-replica "
                        "batcher, byte-identical behavior")
    p.add_argument("--serve-fault-spec", default=None, metavar="SPEC",
                   help="--serve: seeded fault injection into the fleet "
                        "(forces fleet supervision even at 1 replica). "
                        "SPEC is 'kind:key=val,...[;kind:...]' with kind "
                        "crash|stall|nanlogits and keys replica=N plus "
                        "iter=K (K-th decode iteration) / prefill=K / "
                        "verify=K (crash between verify and commit) / "
                        "prob=P (seeded Bernoulli) / stall_s=S.  E.g. "
                        "'crash:replica=0,iter=3'.  The chaos-test "
                        "substrate: every offered request must still "
                        "complete exactly once on the survivors.  NB "
                        "stall faults are only DETECTED (fenced + failed "
                        "over) when --serve-watchdog is set; without it "
                        "the stall just runs its course")
    p.add_argument("--serve-watchdog", type=float, default=0.0,
                   metavar="S",
                   help="--serve-replicas: supervisor watchdog — fail "
                        "over a replica that made no token progress for "
                        "S seconds while busy (the zombie is FENCED, "
                        "not killed: its late emissions are rejected by "
                        "the journal).  Set S above worst-case first-"
                        "program compile time — the watchdog cannot "
                        "tell a stall from an XLA compile.  0 (default) "
                        "= off")
    p.add_argument("--serve-hot-swap", action="store_true",
                   help="--serve: zero-downtime weight hot-swap drill — "
                        "after half the window completes, each replica "
                        "in turn stops admitting, finishes in-flight, "
                        "swaps the served params between compiled-"
                        "program dispatches (never recompiles, fleet "
                        "never below N-1 admitting replicas) and "
                        "resumes; swap_generations >= 1 in serve_fleet "
                        "proves it.  The drill re-installs the same "
                        "trained params so greedy tokens are unchanged; "
                        "a real rollout passes a new checkpoint")
    p.add_argument("--serve-disaggregate", default=None, metavar="P:D",
                   help="--serve: disaggregated prefill/decode fleet — "
                        "P prefill replicas (admission + chunked "
                        "prefill only) hand finished KV to D decode "
                        "replicas via serialized-block transfer "
                        "(extract_handoff/restore_handoff; int8 scales "
                        "ride along), so decode replicas never share "
                        "an iteration with a long prompt.  Overrides "
                        "--serve-replicas with P+D; the prefix pool "
                        "stays prefill-side.  TTFT is still charged "
                        "arrival -> first token INCLUDING the handoff. "
                        "The serve section gains serve_disagg (handoff "
                        "+ per-role conservation counters)")
    p.add_argument("--serve-routing", default="least-loaded",
                   choices=("least-loaded", "affinity"),
                   help="--serve: fleet router policy.  'affinity' "
                        "keys each request on its first prefix-block "
                        "digest (the prefix pool's chained SHA-256 "
                        "keys) and routes repeats to the replica whose "
                        "pool is already warm, falling back to least-"
                        "loaded for new/short prompts; the serve "
                        "section gains serve_fleet_prefix_hit_rate "
                        "(needs --serve-prefix-cache > 0).  Default "
                        "'least-loaded' is the round-17 router, "
                        "byte-identical")
    p.add_argument("--serve-autoscale", default=None, metavar="MIN:MAX",
                   help="--serve: queue-driven autoscaling — the fleet "
                        "starts MIN serving replicas (the rest of "
                        "--serve-replicas dormant: KV allocated, no "
                        "requests routed) and wakes one when arrived "
                        "queue depth crosses the high-watermark, "
                        "draining one back down when idle.  MAX caps "
                        "serving replicas (0 = fleet size); MAX must "
                        "fit inside --serve-replicas.  The serve "
                        "section gains autoscale (scale events) + "
                        "serve_replica_seconds, the efficiency ledger "
                        "`analyze diff` gates lower-is-better.  "
                        "Composes with --serve-disaggregate: the "
                        "MIN:MAX range drives each role pool "
                        "independently (clamped to the pool's size) "
                        "and serve_replica_seconds splits per role")
    p.add_argument("--serve-multi-step", type=int, default=None,
                   metavar="K",
                   help="--serve: fuse K decode iterations into one "
                        "device dispatch (on-device token feedback + "
                        "EOS/budget deactivation under lax.scan) and "
                        "pipeline the next round's dispatch ahead of "
                        "the current round's token materialization.  "
                        "Greedy streams are bitwise identical to K=1; "
                        "admissions wait at most K fused iterations "
                        "(the staleness trade).  The serve section "
                        "gains serve_dispatches + serve_host_gap_s "
                        "(both gated lower-is-better by `analyze "
                        "diff`).  Default None keeps the per-iteration "
                        "loop, program- and key-identical to round 19")
    p.add_argument("--model-arg", action="append", default=[],
                   metavar="KEY=VALUE",
                   help="extra model constructor field (repeatable), e.g. "
                        "--model-arg hidden=256 --model-arg layers=4; "
                        "values parse as int/float/bool when they look "
                        "like one, else string")
    p.add_argument("-tp", "--tensor-parallel", type=int, default=1,
                   help="shard weight matrices over this many devices "
                        "(Megatron-style TP; MLP family)")
    p.add_argument("-pp", "--pipeline-parallel", type=int, default=1,
                   help="shard model stages over this many devices "
                        "(GPipe-style microbatched pipeline)")
    p.add_argument("--microbatches", type=int, default=4,
                   help="pipeline microbatches per step (bubble = (S-1)/(M+S-1))")
    p.add_argument("--pipeline-schedule", default="gpipe",
                   choices=["gpipe", "1f1b"],
                   help="gpipe: all-fwd-then-all-bwd (AD through the scan); "
                        "1f1b: interleaved fwd/bwd with a fixed S-slot "
                        "activation stash (PipeDream-flush)")
    p.add_argument("--pipeline-hidden", type=int, default=128,
                   help="pipeline stage hidden width")
    p.add_argument("-ep", "--expert-parallel", type=int, default=1,
                   help="shard MoE experts over this many devices "
                        "(GShard/Switch-style EP; --model moe)")
    p.add_argument("--num-experts", type=int, default=8,
                   help="MoE expert count (must divide by -ep)")
    p.add_argument("--aux-weight", type=float, default=0.01,
                   help="MoE load-balance auxiliary loss weight")
    p.add_argument("--router-top-k", type=int, default=1, choices=[1, 2],
                   help="MoE routing: 1 = Switch top-1, 2 = GShard top-2 "
                        "(renormalized gates, priority capacity positions)")
    p.add_argument("--router-z-weight", type=float, default=0.0,
                   help="MoE router z-loss weight (0 disables; ~1e-3 "
                        "stabilizes router logits on long runs)")
    p.add_argument("--grad-compression", default="none",
                   choices=["none", "bf16", "int8"],
                   help="compress the cross-device gradient/parameter "
                        "exchange (parallel/compression.py): bf16 halves "
                        "the collective wire bytes (the exchange runs in "
                        "bf16, widened to f32 after), int8 quarters them "
                        "(per-leaf scale + "
                        "stochastic rounding, f32 master params kept); "
                        "none is bitwise identical to the uncompressed "
                        "path.  Data-parallel and GSPMD engines; the "
                        "pipeline schedules reject it")
    p.add_argument("--precision", default="f32",
                   choices=["f32", "bf16", "bf16-f32master",
                            "fp16-f32master"],
                   help="end-to-end mixed-precision policy "
                        "(parallel/precision.py): param STORAGE + compute "
                        "+ grad-reduce dtypes, distinct from --dtype "
                        "(activations only; a non-f32 policy owns the "
                        "model dtype).  bf16: pure bfloat16 — params AND "
                        "optimizer state halve.  bf16-f32master: bf16 "
                        "storage/compute with a float32 master copy "
                        "inside the optimizer state (the Micikevicius "
                        "mixed-precision recipe) — param bytes halve, "
                        "updates below bf16 resolution still accumulate. "
                        "fp16-f32master: float16 + master + dynamic loss "
                        "scaling (overflow steps are skipped and the "
                        "scale backs off; pair with --health on for the "
                        "anomaly guard).  f32 (default) compiles the "
                        "byte-identical pre-policy programs.  Pipeline "
                        "modes reject non-f32 policies")
    p.add_argument("--grad-bucket-mb", type=float, default=0.0,
                   metavar="MB",
                   help="communication/compute overlap: partition the "
                        "gradient pytree into ~MB-sized buckets in "
                        "reverse-backward order (parallel/overlap.py) so "
                        "each bucket's collective — composed with "
                        "--grad-compression, which then codes per bucket "
                        "— is schedulable behind the remaining backward "
                        "compute (XLA latency-hiding flags are enabled "
                        "on TPU; with --grad-accum K > 1 each "
                        "microbatch's reduce also overlaps the next "
                        "microbatch's backward).  ~4 recommended; 0 "
                        "(default) compiles the exact pre-overlap "
                        "programs.  The run measures and reports the "
                        "exposed-vs-hidden collective split "
                        "(grad_collective_exposed_s); pipeline modes "
                        "reject the flag")
    p.add_argument("--steps-per-call", type=int, default=None,
                   help="steady-state drain: training steps rolled into one "
                        "jitted lax.scan per host dispatch (README "
                        "'steady-state performance').  Default auto: 8, "
                        "downshifting to 1 only for a steps-to-target run "
                        "(its ≤10-step eval resolution needs boundary "
                        "state every step); telemetry (--metrics-path, "
                        "--trace, --watchdog-timeout) rides the chunked "
                        "drain without downshifting")
    p.add_argument("--prefetch", type=int, default=2,
                   help="device-prefetch depth: host batches staged onto "
                        "the mesh this many steps ahead so transfer N+1 "
                        "overlaps compute N (data/device_prefetch.py)")
    p.add_argument("--result-path", default=None, help="JSONL event sink path")
    p.add_argument("--supervisor", default=None, metavar="HOST[:PORT]",
                   help="report the reference's start/done/results event "
                        "triple to an external supervisor socket (reference "
                        "server.py:121-124; port defaults to 4000).  Distinct "
                        "from -sa, which is the multi-host coordinator")
    p.add_argument("--log-every", type=int, default=50)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--checkpoint-dir", default=None,
                   help="enable TrainState checkpointing to this directory")
    p.add_argument("--checkpoint-every", type=int, default=0,
                   help="steps between checkpoints (0: final only)")
    p.add_argument("--async-checkpoint", default="on", choices=["on", "off"],
                   dest="async_checkpoint",
                   help="'on' (default): checkpoint saves cost the training "
                        "thread only a device snapshot — the device→host "
                        "transfer, atomic Orbax write and retention sweep "
                        "run on a background writer thread, overlapped with "
                        "the next training chunks (at most one save in "
                        "flight; writer errors re-raise at the next "
                        "checkpoint).  'off': the previous synchronous "
                        "blocking-save path, bit-for-bit — same on-disk "
                        "format, restorable either way")
    p.add_argument("--resume", action="store_true",
                   help="restore the latest checkpoint before training")
    p.add_argument("--elastic-restore", action="store_true",
                   help="mesh-shape-independent resume (elastic/"
                        "reshard.py): restore the latest checkpoint onto "
                        "THIS run's mesh whatever mesh wrote it — device "
                        "count and axis layout may both differ within the "
                        "GSPMD engine family — continue the exact batch "
                        "sequence from the checkpoint's data state "
                        "(exactly-once resume; a pre-elastic checkpoint "
                        "restarts the stream with a resume_replay_steps "
                        "warning), and report preemption_lost_s / "
                        "resume_replay_steps in the run report (gated by "
                        "`analyze diff`)")
    p.add_argument("--max-steps-per-lease", type=int, default=0,
                   metavar="N",
                   help="graceful lease drain (elastic/lease.py): stop at "
                        "the first chunk boundary at/after N steps, write "
                        "the final checkpoint (data state included) and "
                        "exit with a structured `preempted` report "
                        "section — relaunch with --elastic-restore to "
                        "continue.  Checkpointed runs also drain on "
                        "SIGTERM (the scheduler's preemption notice) "
                        "whether or not N is set.  Requires "
                        "--checkpoint-dir")
    p.add_argument("--metrics-path", "--metrics", default=None,
                   dest="metrics_path",
                   help="per-step metrics JSONL path (async crash-durable "
                        "sink; records ride the multi-step scan drain, so "
                        "this no longer downshifts --steps-per-call)")
    p.add_argument("--trace", default=None, metavar="PATH",
                   help="structured trace-span JSONL path: a monotonic-"
                        "clock timeline of compile/chunk_dispatch/"
                        "materialize/checkpoint/eval spans plus prefetch "
                        "gauges, with run/host/process ids (README "
                        "'Observability'); span names are mirrored into "
                        "XProf when --profile-dir is also set")
    p.add_argument("--timeline", action="store_true",
                   help="time-series gauge sampler + XLA program ledger "
                        "(README 'Timeline & memory observability'): queue "
                        "depth / KV blocks / replica load series sampled at "
                        "existing loop boundaries (bounded rings, "
                        "self-measured overhead), plus per-compiled-program "
                        "memory_analysis and compile wall-time in the run "
                        "report ('xla' section, peak_hbm_bytes_est / "
                        "compile_total_s).  Host-side only — off compiles "
                        "the exact pre-timeline program set.  Renders "
                        "offline via `analyze timeline` / "
                        "`analyze programs` and as Perfetto counter tracks")
    p.add_argument("--timeline-interval", type=float, default=0.05,
                   metavar="SECONDS",
                   help="minimum seconds between --timeline samples per "
                        "gauge group (default 0.05; 0 = record every "
                        "boundary crossing)")
    p.add_argument("--roofline", action="store_true",
                   help="roofline efficiency ledger (README 'Roofline & "
                        "efficiency accounting'): analytic model FLOPs/"
                        "bytes cost model + device peak table → train_mfu "
                        "on the fit result, serve_prefill_mfu / "
                        "serve_decode_mbu on the serve summary, and a "
                        "per-compiled-program intensity/bound attribution "
                        "table in the run report ('roofline' section; "
                        "renders offline via `analyze roofline`).  On an "
                        "unknown device kind utilizations report null — a "
                        "peak is never invented.  Host-side only — off "
                        "keeps the program set and every summary/report "
                        "key set byte-identical")
    p.add_argument("--profile-dir", default=None,
                   help="write an XLA profiler trace here (TensorBoard/XProf)")
    p.add_argument("--dtype", default="float32",
                   choices=["float32", "f32", "bfloat16", "bf16"],
                   help="model compute dtype; bfloat16 = mixed precision "
                        "(f32 params, bf16 activations on the MXU)")
    p.add_argument("--watchdog-timeout", type=float, default=0.0,
                   help=">0: detect a stalled step loop (no progress for this "
                        "many seconds PER STEP) and emit a 'stall' event — "
                        "the reference deadlocks silently instead.  Under "
                        "--steps-per-call k the loop beats once per chunk "
                        "and the stall budget scales to k × this value")
    p.add_argument("--watchdog-abort", action="store_true",
                   help="on stall, exit(75) after reporting so a supervisor "
                        "can relaunch with --resume (a wedged XLA runtime "
                        "cannot be recovered in-process)")
    p.add_argument("--health", default="off", choices=["off", "on"],
                   help="per-step numeric training-health stats, computed "
                        "ON DEVICE inside the jitted scan and stacked "
                        "through the trajectory like metrics (zero "
                        "downshift): global grad norm, param norm, update "
                        "ratio, non-finite leaf count, loss-spike score "
                        "vs a running EMA (observability/health.py).  "
                        "They ride --metrics-path records and feed "
                        "--on-anomaly; 'off' (default) compiles the exact "
                        "pre-health program")
    p.add_argument("--on-anomaly", default="warn", choices=["warn", "halt"],
                   dest="on_anomaly",
                   help="with --health on: response to a per-step health "
                        "anomaly (non-finite params/grads, update-ratio "
                        "ceiling, loss spike) — 'warn' records structured "
                        "anomaly trace events and a health summary, "
                        "'halt' additionally stops at the offending step. "
                        " Subsumes the loss-only nan guard (README "
                        "'Health monitoring')")
    p.add_argument("--no-nan-guard", action="store_true",
                   help="disable the fatal divergence (NaN/inf) response: "
                        "without --health, skips the legacy loss-only "
                        "check; with --health on + --on-anomaly warn, "
                        "downgrades nonfinite anomalies (which stay fatal "
                        "by default) to record-and-continue")
    p.add_argument("--max-restarts", type=int, default=0,
                   help=">0: on crash, restart from the latest checkpoint up "
                        "to N times (requires --checkpoint-dir + "
                        "--checkpoint-every)")
    return p


def select_engine(args: argparse.Namespace) -> str:
    if args.mode in ("c", "centralized"):
        return "sync" if args.centralized_strategy == "sync" else "async"
    if args.mode in ("d", "decentralized"):
        if args.decentralized_strategy in ("graph", "custom"):
            return "gossip"
        if args.decentralized_strategy in ("sync", "fsdp"):
            return args.decentralized_strategy
        return "allreduce"
    return "sync"  # tpu_pod


def main(argv: list[str] | None = None, *, model_fn=None,
         dataset_fn=None) -> dict:
    """CLI entry.  ``model_fn``/``dataset_fn`` are the reference's user
    plug-in contract (reference README.md:12: "edit model_fn/dataset_fn in
    initializer.py"): when provided they override --model/--dataset."""
    parser = build_parser()
    args = parser.parse_args(argv)
    resolve_compile_cache()

    try:
        model_args = parse_model_args(args.model_arg)
    except argparse.ArgumentTypeError as bad:
        parser.error(str(bad))  # clean usage error + exit 2, not a traceback

    if (args.task_type is None) != (args.server_address is None):
        # the reference dispatches on task_type alone (reference
        # initializer.py:147-155); silently running single-process when one
        # half of the pair is missing would mask a misconfigured pod
        parser.error("-tt/--task_type and -sa/--server_address must be "
                     "given together for a multi-host run")

    if args.task_type is not None and args.server_address is not None:
        # multi-host pod: same SPMD program on every host, coordinated by
        # process 0 — replaces the reference's role-per-machine dispatch
        # (reference initializer.py:147-155)
        from distributed_tensorflow_tpu.parallel import mesh as meshlib

        # process 0 is the coordinator ('server' role); worker i maps to
        # process i+1, so '-tt worker -ti 0' does not collide with the server
        meshlib.multihost_initialize(
            coordinator_address=args.server_address,
            num_processes=args.num_processes,
            process_id=args.task_index + 1 if args.task_type == "worker" else 0,
        )

    config = ExperimentConfig(
        engine=select_engine(args),
        model=args.model,
        dataset=args.dataset,
        model_fn=model_fn,
        dataset_fn=dataset_fn,
        n_devices=args.number_nodes,
        batch_size=args.batch_size,
        epochs=args.epochs,
        learning_rate=args.lr,
        lr_schedule=args.lr_schedule,
        warmup_steps=args.warmup_steps,
        grad_accum=args.grad_accum,
        grad_compression=args.grad_compression,
        precision=args.precision,
        grad_bucket_mb=args.grad_bucket_mb,
        weight_decay=args.weight_decay,
        clip_norm=args.clip_norm,
        sync_every=args.sync_every,
        degree=args.degree,
        seed=args.seed,
        log_every=args.log_every,
        steps_per_call=args.steps_per_call,
        prefetch=args.prefetch,
        result_path=args.result_path,
        supervisor_address=args.supervisor,
        seq_parallel=args.seq_parallel,
        attention_impl=args.attention,
        positional=args.positional,
        kv_heads=args.kv_heads,
        remat=args.remat,
        model_args=model_args,
        tensor_parallel=args.tensor_parallel,
        pipeline_parallel=args.pipeline_parallel,
        microbatches=args.microbatches,
        pipeline_schedule=args.pipeline_schedule,
        pipeline_hidden=args.pipeline_hidden,
        expert_parallel=args.expert_parallel,
        num_experts=args.num_experts,
        aux_weight=args.aux_weight,
        router_top_k=args.router_top_k,
        router_z_weight=args.router_z_weight,
        checkpoint_dir=args.checkpoint_dir,
        checkpoint_every=args.checkpoint_every,
        async_checkpoint=args.async_checkpoint == "on",
        resume=args.resume,
        elastic_restore=args.elastic_restore,
        max_steps_per_lease=args.max_steps_per_lease,
        metrics_path=args.metrics_path,
        trace_path=args.trace,
        timeline=args.timeline,
        timeline_interval=args.timeline_interval,
        roofline=args.roofline,
        profile_dir=args.profile_dir,
        dtype=args.dtype,
        watchdog_timeout=args.watchdog_timeout,
        watchdog_abort=args.watchdog_abort,
        nan_guard=not args.no_nan_guard,
        health=args.health,
        on_anomaly=args.on_anomaly,
        max_restarts=args.max_restarts,
        sample_tokens=args.sample,
        sample_prompt_len=args.sample_prompt_len,
        serve_requests=args.serve,
        serve_slots=args.serve_slots,
        serve_max_new=args.serve_max_new,
        serve_prompt_len=args.serve_prompt_len,
        serve_kv_dtype=args.serve_kv_dtype,
        serve_prefill_chunk=args.serve_prefill_chunk,
        serve_prefix_cache=args.serve_prefix_cache,
        serve_prefix_block=args.serve_prefix_block,
        serve_shared_prefix=args.serve_shared_prefix,
        serve_slo_ttft=args.serve_slo_ttft,
        serve_slo_itl=args.serve_slo_itl,
        serve_queue_cap=args.serve_queue_cap,
        serve_draft_config=args.serve_draft_config,
        serve_draft_k=args.serve_draft_k,
        serve_replicas=args.serve_replicas,
        serve_fault_spec=args.serve_fault_spec,
        serve_hot_swap=args.serve_hot_swap,
        serve_watchdog_s=args.serve_watchdog,
        serve_disaggregate=args.serve_disaggregate,
        serve_routing=args.serve_routing,
        serve_autoscale=args.serve_autoscale,
        serve_multi_step=args.serve_multi_step,
    )
    summary = run(config)  # run() itself wraps recovery when max_restarts>0
    print(json.dumps(summary))
    return summary


if __name__ == "__main__":
    main()

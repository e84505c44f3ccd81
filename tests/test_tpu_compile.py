"""Programs of the serving path compiled for the TPU v5e without the chip.

libtpu compiles for a chip that is described and not attached
(``jax.experimental.topologies``), so what the v5e's compiler does with a
program at its real widths is checked here, on the CPU: which copies it
puts in, how many temporaries it needs.  These are the compiler's counts,
not device metrics.

The topology is described inside a fixture and nowhere at import: only one
process at a time may load libtpu, and every xdist worker imports this
file.  Keep every such test in this one file, so that one worker holds the
library (a second file could go to a worker whose fixture then skips).
"""

import json
import os
import re
from pathlib import Path

import pytest

import jax
import jax.numpy as jnp

from distributed_tensorflow_tpu.models import create_model, mla_moe
from distributed_tensorflow_tpu.serving import SlotKVCache


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no libtpu here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


class _ProgramProbe(SlotKVCache):
    """Keeps the functions ``SlotKVCache`` hands to ``jax.jit``, with their
    jit arguments, so that a test can lower the very same program for a
    described device."""

    def _jit(self, fn, name, **jit_kwargs):
        self.__dict__.setdefault("programs", {})[name] = (fn, jit_kwargs)
        return super()._jit(fn, name, **jit_kwargs)


def _shapes_on(sharding):
    """``on_chip(shape, dtype)`` and ``like(tree)``: shapes placed on the
    described device (it holds no array)."""
    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    def like(tree):
        return jax.tree.map(lambda t: on_chip(t.shape, t.dtype), tree)

    return on_chip, like


def test_decode_step_writes_the_slot_table_in_place(one_chip):
    """The serve cell's decode step (gpt2-large widths, 32 slots x 1024,
    bf16 table, a float32 checkpoint held as the step uses it, greedy, the
    table donated) at 2 layers:
    the v5e compiler keeps every table leaf in the layout it arrived in,
    and the new K/V row reaches it inside the attention's own two passes.

    The table is ``bf16[32,1024,20,64]`` with ``max_len`` as the minor
    dimension on the chip.  (i) A write the compiler cannot do in that
    layout costs two ``copy`` of the leaf (84 MB, 268 MB when re-laid with
    heads and head_dim minor) and holds both as temporaries: 144 copies,
    7.45 GB of temporaries and 50 GB moved a step at the cell's 36 layers,
    which is what ``.at[rows, pos].set`` did.  (ii) A scatter it can do in
    that layout (``write_slot_rows``, until PR 32) is a ``while`` of 32
    ``dynamic-update-slice`` a leaf, one position's 1,280 values in 1,280
    tiles: 2,304 serial iterations, nearly half of a 35 ms round on the
    chip (PERF.md section 6).  ``select_slot_row`` leaves neither: no ``copy``
    of a leaf, no ``while``, no ``dynamic-update-slice``.  The vocabulary
    is cut to 8,192 (the whole one: the test after the next)."""
    slots, max_len, heads, head_dim = 32, 1024, 20, 64
    model = create_model("gpt", dtype="bfloat16", vocab_size=8192,
                         max_len=max_len, hidden=heads * head_dim, layers=2,
                         heads=heads, ffn=4 * heads * head_dim)
    params = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32),
                           train=False))["params"]
    kv = _ProgramProbe(model, params, slots, greedy=True,
                       kv_dtype=jnp.bfloat16)
    step, jit_kwargs = kv.programs["kv_decode_step"]

    on_chip, like = _shapes_on(one_chip)

    compiled = jax.jit(step, **jit_kwargs).lower(
        like(kv.params), like(kv.cache), on_chip((slots,), jnp.int32),
        on_chip((slots,), jnp.int32), on_chip((slots,), jnp.bool_),
        like(jax.random.key(0))).compile()

    leaf_bytes = slots * max_len * heads * head_dim * 2
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= 4 * leaf_bytes     # donated
    text = compiled.as_text()
    leaf = rf"bf16\[{slots},{max_len},{heads},{head_dim}\]"
    copies = re.findall(rf"= {leaf}\{{[^}}]*\}} copy\(", text)
    assert not copies, f"{len(copies)} relayout copies of a table leaf"
    assert not re.findall(r" while\(", text)
    assert "dynamic-update-slice" not in text
    assert memory.temp_size_in_bytes < leaf_bytes, (
        memory.temp_size_in_bytes, leaf_bytes)


def test_the_block_prefill_is_one_forward_that_writes_the_slot_in_place(
        one_chip):
    """The serve cell's ``kv_prefill_batched_l512`` at the widths and depth
    of ``benchmarks/configs/gpt2-large.json`` (32 slots x 1,024, bf16
    table, a float32 checkpoint held as the step uses it, greedy, the table
    donated).

    Until PR 30 the program was a ``while`` of 512 one-token steps, each
    reading all 3.1 GB of weights and computing a 50,257-wide logits row:
    1.38 s on the chip and 2.24 GB of temporaries.  It is now one forward
    over the block: (i) no ``while`` is left, (ii) the block's K and V
    reach the donated table without a ``copy`` of a whole
    ``bf16[32,1024,20,64]`` leaf (the relayout PR 26 took out of the
    step: 84 MB a leaf, 72 leaves), and (iii) the temporaries (0.26 GB
    while the program made its own bf16 copy of the tied embedding, 129 MB,
    beside the block's activations) stay under 0.5 GB."""
    config = json.loads((Path(__file__).resolve().parent.parent / "benchmarks"
                         / "configs" / "gpt2-large.json").read_text())
    slots, lpad = 32, 512
    max_len, heads = config["n_positions"], config["n_head"]
    hidden = config["n_embd"]
    head_dim = hidden // heads
    model = create_model("gpt", dtype="bfloat16",
                         vocab_size=config["vocab_size"], max_len=max_len,
                         hidden=hidden, layers=config["n_layer"], heads=heads,
                         ffn=4 * hidden)
    params = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32),
                           train=False))["params"]
    kv = _ProgramProbe(model, params, slots, greedy=True,
                       kv_dtype=jnp.bfloat16)
    kv._prefill(lpad)
    prefill, jit_kwargs = kv.programs[f"kv_prefill_batched_l{lpad}"]

    on_chip, like = _shapes_on(one_chip)

    compiled = jax.jit(prefill, **jit_kwargs).lower(
        like(kv.params), like(kv.cache), on_chip((), jnp.int32),
        on_chip((lpad,), jnp.int32), on_chip((), jnp.int32),
        like(jax.random.key(0))).compile()

    text = compiled.as_text()
    assert not re.findall(r" while\(", text)
    leaf_bytes = slots * max_len * heads * head_dim * 2
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= 72 * leaf_bytes    # donated
    leaf = rf"bf16\[{slots},{max_len},{heads},{head_dim}\]"
    copies = re.findall(rf"= {leaf}\{{[^}}]*\}} copy\(", text)
    assert not copies, f"{len(copies)} relayout copies of a table leaf"
    assert memory.temp_size_in_bytes < 0.5e9, memory.temp_size_in_bytes


def test_gpt2_programs_read_the_weights_as_bfloat16(one_chip):
    """The GPT-2 serve cells' decode step and 512 block prefill at 2 layers
    and the WHOLE vocabulary, lowered with the tree the table holds.

    The cell's checkpoint is float32 and its model computes in bfloat16.
    Lowered with the checkpoint as given, every program prefetched the
    ``f32[1280,1280]`` / ``f32[1280,5120]`` / ``f32[5120,1280]`` kernels at
    four bytes a weight, converted each inside its matmul fusion, and made
    and read back a 129 MB bfloat16 copy of the tied ``f32[50257,1280]``
    embedding: 134.6 MB of temporaries in the step, and on the chip 8 ms
    of a 25.6 ms round and of every prefill bucket (PERF.md section 6,
    PR 36).  The table narrows the tree once (``_place_params``): no
    float32 weight operand is left in either program, no temporary the
    size of the embedding, and the arguments are the table, the held tree
    and a few vectors, the held tree half of the checkpoint."""
    config = json.loads((Path(__file__).resolve().parent.parent / "benchmarks"
                         / "configs" / "gpt2-large.json").read_text())
    slots, lpad, layers = 32, 512, 2
    max_len, heads = config["n_positions"], config["n_head"]
    hidden, vocab = config["n_embd"], config["vocab_size"]
    model = create_model("gpt", dtype="bfloat16", vocab_size=vocab,
                         max_len=max_len, hidden=hidden, layers=layers,
                         heads=heads, ffn=4 * hidden)
    params = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32),
                           train=False))["params"]
    kv = _ProgramProbe(model, params, slots, greedy=True,
                       kv_dtype=jnp.bfloat16)
    kv._prefill(lpad)
    on_chip, like = _shapes_on(one_chip)
    slot_vec = on_chip((slots,), jnp.int32)
    scalar = on_chip((), jnp.int32)
    lowered_with = {
        "kv_decode_step": (slot_vec, slot_vec, on_chip((slots,), jnp.bool_)),
        f"kv_prefill_batched_l{lpad}": (scalar, on_chip((lpad,), jnp.int32),
                                        scalar)}

    given = sum(t.size * t.dtype.itemsize for t in jax.tree.leaves(params))
    assert 0.5 * given < kv.param_bytes < 0.501 * given  # LayerNorm: float32
    table = 2 * layers * slots * max_len * hidden * 2
    embedding_copy = vocab * hidden * 2
    # an operand: a parameter of the program or of one of its fusions (a
    # fusion may still widen what it has read, in registers)
    wide = re.compile(
        rf"= (f32\[(?:{hidden},{hidden}|{hidden},{4 * hidden}|{4 * hidden},"
        rf"{hidden}|{vocab},{hidden}|{max_len},{hidden})\])\S* parameter\(")
    for name, vectors in lowered_with.items():
        program, jit_kwargs = kv.programs[name]
        compiled = jax.jit(program, **jit_kwargs).lower(
            like(kv.params), like(kv.cache), *vectors,
            like(jax.random.key(0))).compile()
        left = sorted(set(wide.findall(compiled.as_text())))
        assert not left, (name, left)
        memory = compiled.memory_analysis()
        assert memory.temp_size_in_bytes < embedding_copy, (
            name, memory.temp_size_in_bytes)
        assert (kv.param_bytes + table <= memory.argument_size_in_bytes
                < kv.param_bytes + table + 1e6), (
            name, memory.argument_size_in_bytes)


def test_the_long_prefill_holds_no_score_tile_wider_than_the_key_block(
        one_chip):
    """The long-document cell's ``kv_prefill_batched_l8192`` (the published
    widths of ``benchmarks/configs/kanana-2-30b-a3b-6l.json``, 32 slots x
    8,192 latents, bfloat16) at ONE layer, the leading dense one: the
    expanded attention is the same in every layer.

    Until PR 28 a 512-query block met all its keys in one piece and the
    compiled program held ``f32[32,512,L]`` scores up to ``L`` = 8,192: on
    the chip the softmax fusion over such a tile took 47 ms where its bytes
    need 1.3 ms, and 1.5 ms up to ``L`` = 4,096 (PERF.md section 5).  The
    keys now come ``ATTN_KEY_BLOCK`` at a time: no wider tile is left, and
    the temporaries fall from 1.55 GB to 1.04 GB at this depth."""
    config = json.loads((Path(__file__).resolve().parent.parent / "benchmarks"
                         / "configs" / "kanana-2-30b-a3b-6l.json").read_text())
    slots, lpad, heads = 32, 8192, config["num_attention_heads"]
    model = create_model(
        "mla_moe", dtype="bfloat16", param_dtype="bfloat16", max_len=lpad,
        vocab_size=config["vocab_size"], hidden=config["hidden_size"],
        layers=1, first_dense=1, heads=heads,
        qk_nope_dim=config["qk_nope_head_dim"],
        qk_rope_dim=config["qk_rope_head_dim"], v_dim=config["v_head_dim"],
        kv_rank=config["kv_lora_rank"], dense_ffn=config["intermediate_size"])
    params = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32),
                           train=False))["params"]
    kv = _ProgramProbe(model, params, slots, greedy=True,
                       kv_dtype=jnp.bfloat16)
    kv._prefill(lpad)
    prefill, jit_kwargs = kv.programs[f"kv_prefill_batched_l{lpad}"]

    on_chip, like = _shapes_on(one_chip)

    compiled = jax.jit(prefill, **jit_kwargs).lower(
        like(params), like(kv.cache), on_chip((), jnp.int32),
        on_chip((lpad,), jnp.int32), on_chip((), jnp.int32),
        like(jax.random.key(0))).compile()

    tiles = {int(keys) for keys in re.findall(
        rf"f32\[{heads},{mla_moe.ATTN_QUERY_BLOCK},(\d+)\]",
        compiled.as_text())}
    assert max(tiles) == mla_moe.ATTN_KEY_BLOCK, sorted(tiles)
    assert compiled.memory_analysis().temp_size_in_bytes < 1.2e9


def _window_cell(one_chip, slots=32, max_len=16384):
    """The window-and-global cell's table at its published widths
    (``benchmarks/configs/command-a-plus-05-2026-4l-ep8.json``, 32 slots x
    16,384, bfloat16): ``(probe, params, cache shapes on the chip)``.  The
    probe is built at ONE slot (its table is real arrays here) and the
    programs lowered at 32: they name no slot count."""
    from benchmarks.drivers import window_moe_tree

    config = json.loads((Path(__file__).resolve().parent.parent / "benchmarks"
                         / "configs" / "command-a-plus-05-2026-4l-ep8.json"
                         ).read_text())
    model = create_model("window_moe", dtype="bfloat16",
                         param_dtype="bfloat16",
                         **window_moe_tree.model_kwargs(config, max_len))
    params = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32),
                           train=False))["params"]
    kv = _ProgramProbe(model, params, 1, greedy=True, kv_dtype=jnp.bfloat16)
    on_chip, like = _shapes_on(one_chip)
    cache = jax.tree.map(
        lambda t: on_chip((slots,) + t.shape[1:], t.dtype), kv.cache)
    return kv, like(params), cache


def test_rings_and_full_rows_fit_one_chip_and_the_step_copies_neither(
        one_chip):
    """``serve-commandaplus-mixedlen``'s decode step: 9.47 GB of weights and
    3.76 GB of table (three layers of 4,096-row rings and one of 16,384
    rows: four full-length tables would be 8.59 GB and would not fit beside
    the weights) are the program's arguments, the table is donated, and the
    temporaries stay under 0.5 GB: the eight new rows reach the table as
    eight loops of in-place row writes, and no leaf is copied out of a
    fusion (the attention re-lays its operand inside its own)."""
    slots = 32
    kv, params, cache = _window_cell(one_chip, slots)
    step, jit_kwargs = kv.programs["kv_decode_step_routed"]
    on_chip, like = _shapes_on(one_chip)
    compiled = jax.jit(step, **jit_kwargs).lower(
        params, cache, on_chip((slots,), jnp.int32),
        on_chip((slots,), jnp.int32), on_chip((slots,), jnp.bool_),
        like(jax.random.key(0))).compile()
    memory = compiled.memory_analysis()
    table = slots * (3 * 4096 + 16384) * 2 * 8 * 128 * 2
    assert table == 3_758_096_384
    assert memory.alias_size_in_bytes == table              # donated
    assert 13.2e9 < memory.argument_size_in_bytes < 13.3e9
    assert memory.temp_size_in_bytes < 0.5e9, memory.temp_size_in_bytes
    entry = compiled.as_text().split("\nENTRY ")[1]
    assert len(re.findall(r" while\(", entry)) == 8
    assert not re.findall(
        r"= bf16\[32,(?:4096|16384),8,128\]\{[^}]*\} copy\(", entry)


def test_the_longest_bucket_fits_beside_weights_and_table(one_chip):
    """``kv_prefill_batched_l16384`` of the same cell: with the expert
    layer taken 2,048 tokens at a time and attention 512 queries against
    512 keys at a time, the temporaries stay under 3 GB and the program
    under the chip's 16 GiB (one 131,072-row gather of the sorted pairs
    and its four companions alone would be 5.4 GB)."""
    slots, lpad = 32, 16384
    kv, params, cache = _window_cell(one_chip, slots)
    kv._prefill(lpad)
    prefill, jit_kwargs = kv.programs[f"kv_prefill_batched_l{lpad}"]
    on_chip, like = _shapes_on(one_chip)
    compiled = jax.jit(prefill, **jit_kwargs).lower(
        params, cache, on_chip((), jnp.int32), on_chip((lpad,), jnp.int32),
        on_chip((), jnp.int32), like(jax.random.key(0))).compile()
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes == 3_758_096_384      # donated
    assert memory.temp_size_in_bytes < 3.0e9, memory.temp_size_in_bytes
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            < 16 * 2 ** 30)
    # no score tile wider than the block, over all 128 heads at once
    tiles = {int(keys) for keys in re.findall(
        r"f32\[1,8,16,512,(\d+)\]", compiled.as_text())}
    assert tiles and max(tiles) <= 512, sorted(tiles)


def _jamba_cell(one_chip, monkeypatch, slots, max_len=32768):
    """The selective-scan cell's table at its published widths
    (``benchmarks/configs/ai21-jamba2-3b.json``, 32 slots x 32,768,
    bfloat16), as ``_window_cell``.  The model asks
    ``ops/flash_attention.resolve_interpret`` whether a chip is attached
    and would take its interpreter here: the test steers it to Mosaic."""
    from benchmarks.drivers import jamba_tree
    from distributed_tensorflow_tpu.ops import selective_scan

    monkeypatch.setattr(selective_scan, "resolve_interpret",
                        lambda interpret: False)
    config = json.loads((Path(__file__).resolve().parent.parent / "benchmarks"
                         / "configs" / "ai21-jamba2-3b.json").read_text())
    model = create_model("jamba", dtype="bfloat16", param_dtype="bfloat16",
                         **jamba_tree.model_kwargs(config, max_len))
    params = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32),
                           train=False))["params"]
    kv = _ProgramProbe(model, params, 1, greedy=True, kv_dtype=jnp.bfloat16)
    on_chip, like = _shapes_on(one_chip)
    cache = jax.tree.map(
        lambda t: on_chip((slots,) + t.shape[1:], t.dtype), kv.cache)
    return kv, like(params), cache


def test_the_selective_scan_kernel_compiles_at_the_cells_widths(
        one_chip, monkeypatch):
    """``ops/selective_scan`` at 4,096 positions x 5,120 channels x 16
    (one piece of a prefill): Mosaic takes the kernel (state tiles as a
    loop's carry, ``B`` and ``C`` as scalars in SMEM, a dynamic index on the
    untiled position axis), and the compiled text names the custom call as
    ``benchmarks/metrics/kernel.selective_scan_roofline.json`` finds it: by
    the ``pallas_call``'s name, with the call's own sizes in its result."""
    from distributed_tensorflow_tpu.ops import selective_scan

    monkeypatch.setattr(selective_scan, "resolve_interpret",
                        lambda interpret: False)
    on_chip, _ = _shapes_on(one_chip)
    f32 = lambda *shape: on_chip(shape, jnp.float32)
    length, d, n = 4096, 5120, 16
    text = jax.jit(selective_scan.selective_scan).lower(
        on_chip((1, length, d), jnp.bfloat16), f32(1, length, d), f32(d, n),
        f32(1, length, n), f32(1, length, n), f32(d),
        f32(1, d, n)).compile().as_text()
    pattern = json.loads((
        Path(__file__).resolve().parent.parent / "benchmarks" / "metrics"
        / "kernel.selective_scan_roofline.json").read_text())["pattern"]
    calls = [re.search(pattern, line.strip()) for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 1 and calls[0] is not None
    assert {k: int(v) for k, v in calls[0].groupdict().items()} == {
        "batch": 1, "length": length, "groups": d // 128}


def test_the_32768_bucket_fits_and_loops_over_no_position(one_chip,
                                                          monkeypatch):
    """``kv_prefill_batched_l32768`` of ``serve-jamba2-longctx``: 6.06 GB of
    weights and 1.37 GB of table are the program's arguments, the table is
    donated, and the temporaries stay under 1.5 GB (in one piece the bucket
    needs 12.6 GB, and with a stacked output a layer 9 GB: models/jamba.py).
    The 26 state-space layers each call the kernel once a piece, and the
    only loops are over pieces (8 a layer and a feed-forward) and attention
    blocks (64): none over the bucket's positions."""
    slots, lpad = 32, 32768
    kv, params, cache = _jamba_cell(one_chip, monkeypatch, slots)
    kv._prefill(lpad)
    prefill, jit_kwargs = kv.programs[f"kv_prefill_batched_l{lpad}"]
    on_chip, like = _shapes_on(one_chip)
    compiled = jax.jit(prefill, **jit_kwargs).lower(
        params, cache, on_chip((), jnp.int32), on_chip((lpad,), jnp.int32),
        on_chip((), jnp.int32), like(jax.random.key(0))).compile()
    memory = compiled.memory_analysis()
    table = slots * (2 * 2 * lpad * 128 * 2
                     + 26 * (5120 * 16 * 4 + 3 * 5120 * 2))
    assert table == 1_371_930_624
    assert memory.alias_size_in_bytes == table              # donated
    assert 7.4e9 < memory.argument_size_in_bytes < 7.5e9
    assert memory.peak_memory_in_bytes < 9.0e9, memory.peak_memory_in_bytes
    text = compiled.as_text()
    assert len(re.findall(r'custom_call_target="tpu_custom_call"', text)) == 26
    # 26 loops over a mixer's pieces, 28 over a feed-forward's, and the two
    # attention layers' two nested loops over blocks: a loop a position
    # would be one more a state-space layer
    assert len(re.findall(r" while\(", text)) == 26 + 28 + 2 * 2


def test_flash_kernels_compile_at_the_training_cells_shape(one_chip):
    """The three flash kernels at the training cells' call (batch 8, 16
    heads, L 1,024, head 64, bfloat16, causal, no key mask: ``(128, 1024,
    64)`` inside) compile for the v5e with the blocks `_choose_blocks`
    picks, and the compiled text still holds the three custom calls by the
    result signatures ``benchmarks/metrics/kernel.flash_fwd_roofline.json``
    and ``kernel.flash_bwd_roofline.json`` find them by: one ``(bf16,
    f32)`` (output and lse) and two that the backward pattern matches, one
    ``bf16`` (dQ) and one ``(bf16, bf16)`` (dK, dV)."""
    from distributed_tensorflow_tpu.ops import flash_attention

    on_chip, _ = _shapes_on(one_chip)
    x = on_chip((8, 1024, 16, 64), jnp.bfloat16)

    def step(q, k, v, do):
        # interpret=False: held to the CPU, the wrapper would hand the
        # kernels to the interpreter
        out, vjp = jax.vjp(lambda q, k, v: flash_attention(
            q, k, v, causal=True, interpret=False), q, k, v)
        return out, vjp(do)

    text = jax.jit(step).lower(x, x, x, x).compile().as_text()
    calls = [line.strip() for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 3, calls

    def matching(metric):
        spec = json.loads((Path(__file__).parent.parent / "benchmarks"
                           / "metrics" / f"{metric}.json").read_text())
        # the name is the flax module's in the training step and jvp's
        # here; everything after it is the metric's own pattern
        name, rest = spec["pattern"].split(" = ", 1)
        assert name.startswith("^%CausalSelfAttention")
        return [c for c in calls if re.search(r"^%[\w.\-]+ = " + rest, c)]

    forward = matching("kernel.flash_fwd_roofline")
    backward = matching("kernel.flash_bwd_roofline")
    assert len(forward) == 1 and len(backward) == 2, (forward, backward)
    assert not set(forward) & set(backward)
    assert re.search(r"= \(bf16\[128,64,1024\]\{[^}]*\}, f32\[128,1,1024\]",
                     forward[0])
    dq, dkv = sorted(backward, key=lambda c: "= (" in c)
    assert re.search(r"= bf16\[128,64,1024\]", dq)
    assert re.search(
        r"= \(bf16\[128,1024,64\]\{[^}]*\}, bf16\[128,1024,64\]", dkv)

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

import os
import re

import pytest

import jax
import jax.numpy as jnp

from distributed_tensorflow_tpu.models import create_model
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


def test_decode_step_writes_the_slot_table_in_place(one_chip):
    """The serve cell's decode step (gpt2-large widths, 32 slots x 1024,
    bf16 table, float32 parameters, greedy, the table donated) at 2 layers:
    the v5e compiler keeps every table leaf in the layout it arrived in.

    The table is ``bf16[32,1024,20,64]`` with ``max_len`` as the minor
    dimension on the chip.  A write the compiler cannot do in that layout
    costs two ``copy`` of the leaf (84 MB, 268 MB when re-laid with heads
    and head_dim minor) and holds both as temporaries: 144 copies, 7.45 GB
    of temporaries and 50 GB moved a step at the cell's 36 layers, which
    is what ``.at[rows, pos].set`` did (PERF.md section 5).  The vocabulary
    is cut to 8,192: the bf16 copy of the tied embedding is a temporary of
    its own, 129 MB at 50,257, and not what this test is about."""
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

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def like(tree):
        return jax.tree.map(lambda t: on_chip(t.shape, t.dtype), tree)

    compiled = jax.jit(step, **jit_kwargs).lower(
        like(params), like(kv.cache), on_chip((slots,), jnp.int32),
        on_chip((slots,), jnp.int32), on_chip((slots,), jnp.bool_),
        like(jax.random.key(0))).compile()

    leaf_bytes = slots * max_len * heads * head_dim * 2
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= 4 * leaf_bytes     # donated
    leaf = rf"bf16\[{slots},{max_len},{heads},{head_dim}\]"
    copies = re.findall(rf"= {leaf}\{{[^}}]*\}} copy\(", compiled.as_text())
    assert not copies, f"{len(copies)} relayout copies of a table leaf"
    assert memory.temp_size_in_bytes < leaf_bytes, (
        memory.temp_size_in_bytes, leaf_bytes)

"""Pallas TPU kernels for the hot ops.

The reference has no native/accelerated code at all (SURVEY.md §2: 100%
Python, the fast path is whatever tf.keras does) — this package is the
TPU-native answer: hand-written Pallas kernels where XLA's automatic fusion
leaves throughput on the table, starting with flash attention (the O(L)
-memory attention that BERT + sequence parallelism ride on); GPT-2's
one-token step over the slot table, read to the prefix the round needs
(``ops.slot_attention``); the
selective scan (the Mamba-1 recurrence, which has
no matrix-product form: the state stays in VMEM across the sequence;
``ops.selective_scan`` is the module, its entry ``selective_scan.selective_scan``);
and DeepSeek-style sparse attention, whose learned indexer keeps the top-k
keys of each query (``ops.sparse_attention``: an exact selection by
bisection over the scores' bits in the prefill, a step that reads each
slot's index rows to its length and attends to the selected rows only).
"""

from distributed_tensorflow_tpu.ops.flash_attention import flash_attention  # noqa: F401

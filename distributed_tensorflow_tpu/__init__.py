"""distributed_tensorflow_tpu — a TPU-native distributed training framework.

A from-scratch JAX/XLA re-design of the capabilities of the reference
framework jpadrao/distributed-tensorflow (parameter-server sync/async DP and
collective-allreduce DP over TCP/pickle and TF RING collectives —
/root/reference/centralized/server.py, /root/reference/decentralized/native/
dist_keras.py).  Here every training mode is a single-program multiple-data
(SPMD) program over a `jax.sharding.Mesh`; gradients/parameters ride ICI via
XLA collectives (`psum`/`ppermute`) instead of pickled TCP messages.

Layering (SURVEY.md §7.2):
  L0  parallel.mesh         — device discovery, Mesh construction, multi-host init
  L1  parallel.collectives  — named collective wrappers (the "wire" replacement)
  L2  engines.*             — sync / async-local / allreduce / gossip step engines
  L3  models.*, data.*      — model_fn / dataset_fn plug-in points
  L4  cli                   — initializer.py-compatible launcher
  L5  utils.harness         — timing window, eval, supervisor-style reporting
"""

__version__ = "0.1.0"


from distributed_tensorflow_tpu.parallel import mesh, collectives  # noqa: F401

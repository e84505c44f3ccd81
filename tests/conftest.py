"""Test harness: fake 8-device CPU mesh.

The SPMD analogue of the reference's fake cluster (fork + loopback TCP,
reference initializer.py:134-145): we expose 8 XLA host-platform devices so
every multi-device code path runs on CPU.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from __graft_entry__ import _force_cpu_mesh  # noqa: E402

_force_cpu_mesh(8)

import jax  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def mesh8():
    from distributed_tensorflow_tpu.parallel import mesh as meshlib

    return meshlib.create_mesh(8)


@pytest.fixture(scope="session")
def devices():
    return jax.devices()

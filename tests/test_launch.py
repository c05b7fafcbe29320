"""Multi-host launch wiring (SURVEY.md §3.3/§4.1: the L6 layer).

The env-contract parser is unit-tested directly; the actual
``jax.distributed.initialize`` path is exercised by a REAL two-process CPU
rendezvous (subprocesses, TCP coordinator on localhost) — the same
"test the real collective path, not a mock" strategy the 8-device rig uses.
"""

import os
import socket
import subprocess
import sys

import pytest

from apex_example_tpu.parallel.launch import _parse_env

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestParseEnv:
    def test_no_env_is_single_process(self):
        assert _parse_env({}) is None

    def test_jax_native_address_only(self):
        kw = _parse_env({"JAX_COORDINATOR_ADDRESS": "10.0.0.1:1234"})
        assert kw == {"coordinator_address": "10.0.0.1:1234"}

    def test_jax_native_full(self):
        kw = _parse_env({"JAX_COORDINATOR_ADDRESS": "h:1",
                         "JAX_NUM_PROCESSES": "4",
                         "JAX_PROCESS_ID": "2"})
        assert kw == {"coordinator_address": "h:1", "num_processes": 4,
                      "process_id": 2}

    def test_torch_names_carry_over(self):
        kw = _parse_env({"MASTER_ADDR": "host0", "MASTER_PORT": "29500",
                         "WORLD_SIZE": "2", "RANK": "1"})
        assert kw == {"coordinator_address": "host0:29500",
                      "num_processes": 2, "process_id": 1}

    def test_torch_world_size_one_collapses(self):
        assert _parse_env({"MASTER_ADDR": "h", "WORLD_SIZE": "1",
                           "RANK": "0"}) is None

    def test_torch_default_port(self):
        kw = _parse_env({"MASTER_ADDR": "h", "WORLD_SIZE": "2", "RANK": "0"})
        assert kw["coordinator_address"].endswith(":12355")


_WORKER = """
import jax
jax.config.update("jax_platforms", "cpu")
from apex_example_tpu.parallel import (is_main_process,
                                       maybe_initialize_distributed)
pid, n = maybe_initialize_distributed()
assert n == 2, n
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
# one global psum across the two processes' devices: the real multi-host
# collective path (global devices > local devices).
devs = jax.devices()
assert len(devs) == 2 and len(jax.local_devices()) == 1
mesh = Mesh(devs, ("data",))
x = jax.make_array_from_callback(
    (2,), NamedSharding(mesh, P("data")),
    lambda idx: jnp.asarray([float(pid + 1)]))
total = jax.jit(lambda a: jnp.sum(a))(x)
assert float(total) == 3.0, float(total)
print(f"proc{pid} main={is_main_process()} OK")
"""


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_two_process_cpu_rendezvous():
    port = _free_port()
    procs = []
    for rank in range(2):
        env = dict(os.environ)
        env.update({
            "PYTHONPATH": REPO,
            "JAX_PLATFORMS": "cpu",
            # torch-style names: the reference-parity contract end to end
            "MASTER_ADDR": "127.0.0.1", "MASTER_PORT": str(port),
            "WORLD_SIZE": "2", "RANK": str(rank),
            "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
        })
        procs.append(subprocess.Popen(
            [sys.executable, "-c", _WORKER], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True))
    outs = [p.communicate(timeout=300) for p in procs]
    for i, (p, (out, err)) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {i} failed:\n{out}\n{err}"
    assert "proc0 main=True OK" in outs[0][0]
    assert "proc1 main=False OK" in outs[1][0]

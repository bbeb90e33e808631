"""Test harness: run everything on a virtual 8-device CPU mesh.

The reference tests all roles on one machine over loopback with
BYTEPS_FORCE_DISTRIBUTED (reference: tests/meta_test.py:27-58). The JAX
analogue: force the CPU platform with 8 virtual devices so every mesh/
collective path is exercised without TPU hardware. Env must be set before
jax initializes its backends, hence module scope here.
"""

import os
import tempfile

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    flags = (flags + " --xla_force_host_platform_device_count=8").strip()
if "xla_cpu_enable_fast_math" not in flags:
    # XLA CPU fast-math reassociates FMA contraction per SHAPE, so the
    # same elementwise math on a (50,7) leaf vs its flat 1/N shards can
    # differ by 1 ULP — which would make the locality-shard parity
    # suites (shard on vs off bitwise) flake on exactly the property
    # they guard. TPU codegen has no fast-math reassociation; pinning
    # it off here makes the CPU harness match the hardware contract.
    flags = (flags + " --xla_cpu_enable_fast_math=false").strip()
os.environ["XLA_FLAGS"] = flags
os.environ.setdefault("BYTEPS_LOG_LEVEL", "WARNING")
# flight-recorder dumps (fatal wire errors fire them automatically)
# land in a temp dir, not the checkout — tests that assert on the dump
# path override this themselves
os.environ.setdefault(
    "BYTEPS_FLIGHT_DIR",
    os.path.join(tempfile.gettempdir(), f"bps-flight-{os.getpid()}"))

import jax  # noqa: E402
import pytest  # noqa: E402

# config.update as well as the env: a caller that imported jax before
# pytest collected this file has already latched the env vars
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs


@pytest.fixture()
def bps():
    """Fresh byteps_tpu init/shutdown around each test."""
    import byteps_tpu as bps_mod
    from byteps_tpu.core.state import GlobalState

    GlobalState._instance = None  # reset singleton between tests
    bps_mod.init()
    yield bps_mod
    bps_mod.shutdown()
    GlobalState._instance = None

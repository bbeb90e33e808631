"""The rehearsal of a cell on more than one chip needs as many devices
in the child process that runs it. ``bench_helpers.child_env`` hands a
child this process's environment less ``XLA_FLAGS`` (where
``tests/conftest.py`` asks for the 8 CPU devices of this process), so
the child of a test that is parametrised by such a cell is given its
devices through JAX's own variable. Every other test's children run as
they did: one CPU device, the mesh built from it."""

import pytest

from bench_helpers import manifest


@pytest.fixture(autouse=True)
def _cpu_devices_for_a_multi_chip_cell(request, monkeypatch):
    params = getattr(getattr(request.node, "callspec", None), "params", {})
    chips = {w["name"]: w["chips"] for w in manifest()["workloads"]}
    if any(chips.get(v, 1) > 1 for v in params.values()
           if isinstance(v, str)):
        monkeypatch.setenv("JAX_NUM_CPU_DEVICES", "8")

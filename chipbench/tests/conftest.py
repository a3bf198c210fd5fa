"""A test parametrised over the benchmark's cells (``cell``) rehearses a
cell that asks for several chips on that many virtual CPU devices: the
rehearsal's child process inherits ``XLA_FLAGS``. Every other test, and
every one-chip cell, runs with the environment it was given."""

import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.fixture(autouse=True)
def virtual_devices_for_the_cells_chips(request, monkeypatch):
    spec = getattr(request.node, "callspec", None)
    cell = spec.params.get("cell") if spec is not None else None
    if cell is None:
        return
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        chips = {w["name"]: w["chips"] for w in json.load(f)["workloads"]}
    if chips.get(cell, 1) > 1:
        monkeypatch.setenv(
            "XLA_FLAGS",
            f"--xla_force_host_platform_device_count={chips[cell]}")

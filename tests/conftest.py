"""Test bootstrap: run JAX on a virtual 8-device CPU mesh.

Mirrors the reference's SharedSparkContext `local[*]` strategy (SURVEY.md §4)
— distributed semantics exercised without real hardware. Must set flags
before jax initializes.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

# The suite runs on the CPU backend (the tier-1 command sets
# JAX_PLATFORMS=cpu; a bare `pytest` gets the same), and so does every
# subprocess a test starts, since they inherit the environment.  The chip
# is reached only through `python chip_smoke.py`.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402


@pytest.fixture()
def mem_storage(monkeypatch):
    """Fresh in-memory Storage bound as the process default."""
    from predictionio_tpu.storage.locator import Storage, StorageConfig, set_storage

    cfg = StorageConfig(
        sources={"MEM": {"type": "memory"}},
        repositories={"METADATA": "MEM", "EVENTDATA": "MEM", "MODELDATA": "MEM"},
    )
    storage = Storage(cfg)
    set_storage(storage)
    yield storage
    set_storage(None)


@pytest.fixture()
def fs_storage(tmp_path):
    from predictionio_tpu.storage.locator import Storage, StorageConfig, set_storage

    cfg = StorageConfig(
        sources={"FS": {"type": "localfs", "path": str(tmp_path / "store")}},
        repositories={"METADATA": "FS", "EVENTDATA": "FS", "MODELDATA": "FS"},
    )
    storage = Storage(cfg)
    set_storage(storage)
    yield storage
    set_storage(None)


@pytest.fixture()
def mesh8():
    from predictionio_tpu.parallel.mesh import MeshSpec, create_mesh

    return create_mesh(MeshSpec(dp=4, mp=2))

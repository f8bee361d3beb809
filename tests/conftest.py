import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import pytest

# NOTE: do NOT set xla_force_host_platform_device_count here — smoke tests
# and benches must see exactly 1 device. Multi-device behaviour is tested in
# subprocesses (tests/test_spmd_subprocess.py) and by the dry-run driver.


def pytest_collection_modifyitems(config, items):
    # requires_accelerator: compiled (non-interpret) Pallas paths need a
    # TPU backend; on the CPU CI they auto-skip instead of failing inside
    # the Mosaic lowering
    if jax.default_backend() == "tpu":
        return
    skip = pytest.mark.skip(reason="needs a TPU backend "
                                   f"(have {jax.default_backend()})")
    for item in items:
        if "requires_accelerator" in item.keywords:
            item.add_marker(skip)


@pytest.fixture
def rng():
    return jax.random.PRNGKey(0)


def make_cloud(tmp_path, chunk_size=64 * 1024, n_servers=6, user="alice"):
    from repro.sector import ChunkServer, SectorClient, SectorMaster

    master = SectorMaster(chunk_size=chunk_size)
    sites = master.topology.sites
    servers = [ChunkServer(f"s{i}", sites[i % len(sites)], tmp_path)
               for i in range(n_servers)]
    for s in servers:
        master.register(s)
    master.acl.add_member(user)
    master.acl.grant_write(user)
    client = SectorClient(master, user, "chicago")
    return master, servers, client

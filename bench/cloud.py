"""The Sector cloud a cell runs on, built from its configuration."""
from __future__ import annotations

USER = "bench"


def make_cloud(root: str, config: dict):
    """``config["chunk_servers"]`` chunk servers spread round-robin over
    the Teraflow sites, storing under ``root``; chunks of
    ``config["chunk_bytes"]`` trimmed to whole records.  Returns
    ``(master, client)``."""
    from repro.sector import ChunkServer, SectorClient, SectorMaster

    record = config["record_bytes"]
    chunk = config["chunk_bytes"] - config["chunk_bytes"] % record
    master = SectorMaster(chunk_size=chunk)
    sites = master.topology.sites
    for i in range(config["chunk_servers"]):
        master.register(ChunkServer(f"s{i}", sites[i % len(sites)], root))
    master.acl.add_member(USER)
    master.acl.grant_write(USER)
    return master, SectorClient(master, USER, "chicago")


def under_replicated(master, file: str, data, replication: int) -> int:
    """Chunks of ``file`` that fewer than ``replication`` chunk servers
    hold byte for byte.  Every replica the master lists for an
    acknowledged upload is read back and compared with ``data``, the
    bytes that were uploaded; a file whose chunks do not add up to
    ``data`` counts one more."""
    view = memoryview(data).cast("B")
    short = offset = 0
    for meta in master.lookup(file, USER):
        want = view[offset:offset + meta.size]
        offset += meta.size
        held = 0
        for sid in set(meta.locations):
            try:
                held += master.servers[sid].read_chunk(meta.chunk_id) == want
            except (KeyError, OSError):   # server gone, chunk missing
                pass
        short += held < replication
    return short + (offset != len(view))

"""Storage: bytes of the adjacency arrays on the device (each relation and
the untyped union, forward and stored transpose; a delta-served handle
counts its frozen base) per stored edge. Moves
device_bytes_per_edge."""

UNIT = "bytes/edge"


def read(obs):
    return obs.storage_bytes / obs.edges

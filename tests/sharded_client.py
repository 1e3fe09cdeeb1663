"""The tests' one way to a client that shards EVERY epoch: a private
mesh plane whose row threshold is 0, and that plane's client for the
storage — the served path's client and the served path's programs, with
nothing left below the threshold."""

from tidb_tpu.copr import mesh as M


def sharded_client(storage, devices=None):
    """A fresh plane (over `devices`, default all) and its client for
    `storage`; each call makes its own, so two of them over one storage
    share no cache."""
    plane = M.MeshPlane(
        M.MeshConfig(enabled=True, shard_threshold_rows=0), devices=devices)
    return plane.client_for(storage)

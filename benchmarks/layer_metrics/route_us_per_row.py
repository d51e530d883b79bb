"""Shard routing: the flight recorder's route seconds over the window (a
sharded engine's keys to shards by CRC-32, the batch regrouped by shard,
one native slot resolve a shard), per row.  A program without the stage,
or one that noted nothing in it (one chip), reports nothing."""


def read(ctx):
    r = ctx["recorder"]
    if not r or not r["rows"] or not r["stage_s"].get("route"):
        return None
    return r["stage_s"]["route"] * 1e6 / r["rows"]

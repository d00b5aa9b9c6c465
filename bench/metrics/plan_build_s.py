"""Plan build: the server's constructor (co-occurrence, grouping,
replication, shard plan, images) until its image stack is on the device,
by the host clock."""

UNIT = "s"
LAYER = "planner (core/cooccurrence, core/grouping, dist/shard_plan)"
MOVES = "setup_s"


def read(m):
    return m.plan_build_s

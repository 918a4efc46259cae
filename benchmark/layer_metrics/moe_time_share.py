"""Device time under the expert-share layer's scopes (moe_router + moe_experts + moe_shared, moe/expert_share.py) over busy time."""

from harness import readers_moe


def read(run):
    return readers_moe.scope_time_share(run, readers_moe.MOE_SCOPES)

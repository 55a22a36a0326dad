"""Model FLOP/s utilization: the operations forward and backward need per
token (recomputation not counted) x tokens/s/chip over the published peak."""
from . import rate


def read(obs):
    per_token = obs["values"].get("flops_per_token")
    if per_token is None:
        return None
    return (100.0 * per_token * rate.read(obs, "whole_steps")
            / obs["peaks"]["flops"])

"""Bytes the one-token state update of a Mamba-2 layer has to move, from
shapes alone (`ops/pallas/ssm_update.py`, kernel `ssm_state_update`). Kept
with the benchmark and beside its reader, not in `costs.py`, which is a file
that was here before the kernel.
"""

from __future__ import annotations

F32 = 4


def ssm_update_bytes(config: dict, active_slots: float) -> float:
    """What ONE call (one Mamba block, one decode step) has to read and write
    at the least: every live sequence's state [H, P, N] in float32 once in
    and once out, and its vectors: the decay [H], dt * x [H, P], B and C
    [G, N] in, y [H, P] out. Slots that are not live cost nothing."""
    H, P, N = config["mamba_num_heads"], config["mamba_head_dim"], config["ssm_state_size"]
    G = config["n_groups"]
    state = 2 * H * P * N * F32
    vectors = (H + 2 * H * P + 2 * G * N) * F32
    return active_slots * (state + vectors)

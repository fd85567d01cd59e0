"""Bytes the one-token state update of a Falcon-H1 Mamba-2 mixer has to move,
from shapes alone (`ops/pallas/ssm_update.py`, kernel `ssm_state_update`): the
accounting of `_ssm_cost.py` (each live sequence's float32 state once in and
once out, and its vectors), read from the `falcon_h1` config's own keys. Kept
beside its reader; `_ssm_cost.py` is a file that was here before this
configuration and reads NemotronH's keys.
"""

from __future__ import annotations

from layer_metrics import _ssm_cost

#: `falcon_h1`'s key for each of the keys `_ssm_cost.py` reads
KEYS = {"mamba_num_heads": "mamba_n_heads", "mamba_head_dim": "mamba_d_head",
        "ssm_state_size": "mamba_d_state", "n_groups": "mamba_n_groups"}


def shapes(config: dict) -> dict:
    """The state's shape under the keys `_ssm_cost.py` reads."""
    return {theirs: config[ours] for theirs, ours in KEYS.items()}


def ssm_update_bytes(config: dict, active_slots: float) -> float:
    """What ONE call (one layer, one decode step) has to read and write at the
    least: `_ssm_cost.ssm_update_bytes` at this configuration's shapes."""
    return _ssm_cost.ssm_update_bytes(shapes(config), active_slots)

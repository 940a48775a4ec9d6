"""IMPALA's deep ResNet-LSTM agent on Atari (Espeholt et al. 2018, Fig. 3
right): the network's widths at their published values."""

from dataclasses import dataclass
from typing import Tuple


@dataclass(frozen=True)
class ImpalaConfig:
    obs_size: int = 84
    obs_channels: int = 4
    num_actions: int = 18
    # one conv stack per entry: conv 3x3, max-pool 3x3 / 2, two residual
    # blocks of two 3x3 convs each
    channels: Tuple[int, ...] = (16, 32, 32)
    res_blocks: int = 2
    fc_dim: int = 256
    core_dim: int = 256

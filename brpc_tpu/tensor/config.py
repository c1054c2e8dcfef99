"""Model / mesh configuration for the flagship tensor-transport model."""
from __future__ import annotations

import dataclasses
from typing import Tuple


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    vocab: int = 256
    d_model: int = 128
    n_heads: int = 4
    d_head: int = 32
    d_ff: int = 256
    n_layers: int = 2  # layers PER pipeline stage
    n_experts: int = 4
    expert_capacity_factor: float = 2.0
    dtype: str = "bfloat16"

    @property
    def d_qkv(self) -> int:
        return self.n_heads * self.d_head


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Logical mesh axes. Sizes multiply to the device count.

    dp: data (batch) replication of params / sharding of batch
    pp: pipeline stages
    tp: tensor (megatron) sharding of heads / ffn
    sp: sequence (context) sharding — ring attention axis
    ep: expert sharding — MoE all_to_all axis
    """

    dp: int = 1
    pp: int = 1
    tp: int = 1
    sp: int = 1
    ep: int = 1

    AXIS_NAMES: Tuple[str, ...] = ("dp", "pp", "tp", "sp", "ep")

    @property
    def shape(self):
        return {"dp": self.dp, "pp": self.pp, "tp": self.tp, "sp": self.sp, "ep": self.ep}

    @property
    def n_devices(self) -> int:
        return self.dp * self.pp * self.tp * self.sp * self.ep

    @classmethod
    def factorize(cls, n: int) -> "MeshSpec":
        """Spread n devices over axes, preferring tp, pp, dp, then sp, ep —
        all five axes exist (size>=1) so every collective path executes."""
        sizes = {"dp": 1, "pp": 1, "tp": 1, "sp": 1, "ep": 1}
        order = ["tp", "pp", "dp", "sp", "ep"]
        i = 0
        while n % 2 == 0 and n > 1:
            sizes[order[i % len(order)]] *= 2
            n //= 2
            i += 1
        sizes["dp"] *= n  # odd remainder rides dp
        return cls(**sizes)


# The flagship at its bench width: compute-bound on one v5e chip and
# within its 16 GB (the step takes ~1.4 GiB of arguments and ~4.6 GiB of
# temporaries), at FLAGSHIP_BATCH = (batch, sequence) per step.
FLAGSHIP = ModelConfig(vocab=32768, d_model=2048, n_heads=16, d_head=128,
                       d_ff=8192, n_layers=8, n_experts=2,
                       expert_capacity_factor=1.25)
FLAGSHIP_BATCH = (4, 512)


def flagship_flops_per_step(cfg: ModelConfig, batch: int, seq: int) -> float:
    """Model FLOPs of one training step (forward matmuls x3 for fwd+bwd).
    Conservative: the MoE one-hot dispatch einsums burn FLOPs that are
    not model FLOPs and are not counted. Per token and layer: attention
    projections 2*4*d*dqkv, scores 2*2*T*dqkv, top-1 MoE 2*2*d*d_ff;
    per token: unembedding 2*d*vocab."""
    d, dq, L = cfg.d_model, cfg.d_qkv, cfg.n_layers
    fwd_per_tok = (L * (2 * 4 * d * dq + 2 * 2 * seq * dq +
                        2 * 2 * d * cfg.d_ff) + 2 * d * cfg.vocab)
    return 3.0 * fwd_per_tok * batch * seq

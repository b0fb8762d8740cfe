"""granite-4.0-h-micro [hybrid] — Mamba-2 + GQA (NoPE), as an ICU ECG member.

Published config (huggingface.co/ibm-granite/granite-4.0-h-micro,
``config.json``, ``model_type`` ``granitemoehybrid``): hidden 2048, 40
layers, Mamba-2 at every index but attention at 5, 15, 25, 35; Mamba-2 64
heads x 64 (d_inner 4096), d_state 128, 1 group, conv 4 with bias, chunk
256; GQA 32 query / 8 KV heads of 64, no positional encoding, scores
scaled by ``attention_multiplier``; a SwiGLU MLP of 8192 in every layer;
RMSNorm eps 1e-5, ``residual_multiplier`` 0.22 on every branch,
``embedding_multiplier`` 12.  For each layer:

    h += 0.22 * mixer(rms(h));   h += 0.22 * mlp(rms(h))

As an ECG member the token embedding gives way to a patch embedding: a
30 s, 3-lead window at 250 Hz is cut into 0.1 s patches (25 samples x 3
leads = 75 values, 300 tokens), each mapped linearly to 2048.  A final
RMSNorm and a 2048 -> 2 head read the last token; softmax[1] is the
member's score, the zoo members' contract.  The default spec keeps the
first 10 layers in published order (one whole period, 9 Mamba-2 : 1
attention); every width is the published one.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

from repro.configs.base import ArchConfig, SSMConfig

SOURCE = ("https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/"
          "main/config.json")

# the published layer pattern (``layer_types``)
LAYER_TYPES: Tuple[str, ...] = tuple(
    "attention" if i in (5, 15, 25, 35) else "mamba" for i in range(40))


@dataclasses.dataclass(frozen=True)
class GraniteHybridSpec:
    """One hybrid member's shapes; frozen, so it keys compile caches."""
    name: str = "granite4h"
    layer_types: Tuple[str, ...] = LAYER_TYPES[:10]
    d_model: int = 2048
    n_heads: int = 32                 # query heads
    n_kv_heads: int = 8
    head_dim: int = 64
    d_ff: int = 8192                  # shared_intermediate_size
    ssm_heads: int = 64               # mamba_n_heads
    ssm_head_dim: int = 64            # mamba_d_head (d_inner 4096)
    d_state: int = 128
    n_groups: int = 1
    d_conv: int = 4
    chunk: int = 256
    attention_multiplier: float = 0.015625
    residual_multiplier: float = 0.22
    embedding_multiplier: float = 12.0
    norm_eps: float = 1e-5
    leads: int = 3
    patch: int = 25                   # samples per token (0.1 s at 250 Hz)
    input_len: int = 7500             # samples per lead per window
    n_classes: int = 2

    @property
    def tokens(self) -> int:
        return self.input_len // self.patch

    @property
    def patch_dim(self) -> int:
        return self.leads * self.patch

    def arch(self) -> ArchConfig:
        """The shapes as the shared Mamba-2 / GQA blocks read them."""
        expand, rem = divmod(self.ssm_heads * self.ssm_head_dim,
                             self.d_model)
        if rem:
            raise ValueError("Mamba-2 d_inner must be a multiple of "
                             "d_model")
        return ArchConfig(
            name=self.name, family="hybrid", source=SOURCE,
            num_layers=len(self.layer_types), d_model=self.d_model,
            n_heads=self.n_heads, n_kv_heads=self.n_kv_heads,
            d_ff=self.d_ff, vocab_size=0, head_dim=self.head_dim,
            ssm=SSMConfig(d_state=self.d_state, head_dim=self.ssm_head_dim,
                          expand=expand, conv_width=self.d_conv,
                          n_groups=self.n_groups, chunk=self.chunk),
            norm_eps=self.norm_eps)

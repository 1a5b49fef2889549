"""Model zoo: pure-JAX pytree models designed for pjit sharding.

The rule. A family that is TRAINED through this package is exported here
(GPT-2, Llama, the sparse ``moe`` model), and so are the two hybrids whose
names older callers import from here (Nemotron-H, Granite 4.0-H). A family
that is only SERVED is not: ``LLMEngine`` resolves every served family by
name (``serve/llm_engine._model_bundle`` lists them), so a process that
serves one family never imports another. Each family's module docstring
says what the family is; ``models/common.py`` holds the helpers the
families share, ``models/prefill.py`` the chunk rule, and
``models/resnet.py`` is imported by its path.

Models are plain functions over parameter pytrees — no framework Module
state — so the same code runs under any mesh and any rules table.
"""

from ray_tpu.models.gpt2 import GPT2Config, gpt2_forward, gpt2_init, gpt2_loss
from ray_tpu.models.llama import (
    LlamaConfig,
    llama_forward,
    llama_init,
    llama_loss,
)
from ray_tpu.models.granite_hybrid import (
    GraniteHybridConfig,
    granite_hybrid_forward,
    granite_hybrid_init,
)
from ray_tpu.models.nemotron_h import (
    NemotronHConfig,
    nemotron_h_forward,
    nemotron_h_init,
)
from ray_tpu.models.moe import (
    MoEConfig,
    moe_forward,
    moe_init,
    moe_loss,
)

__all__ = [
    "GPT2Config",
    "GraniteHybridConfig",
    "LlamaConfig",
    "MoEConfig",
    "NemotronHConfig",
    "gpt2_forward",
    "gpt2_init",
    "gpt2_loss",
    "granite_hybrid_forward",
    "granite_hybrid_init",
    "llama_forward",
    "llama_init",
    "llama_loss",
    "moe_forward",
    "moe_init",
    "moe_loss",
    "nemotron_h_forward",
    "nemotron_h_init",
]

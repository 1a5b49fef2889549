"""Model zoo: pure-JAX pytree models designed for pjit sharding.

Exported here: GPT-2 (the benchmark's training cells, and served), Llama
(served), the sparse ``moe`` model (trained only), and two served-only
hybrids, Nemotron-H (Mamba-2, attention and latent-MoE layers) and Granite
4.0-H (a Mamba-2 mixer or attention, then gated experts, in every layer).

Not exported here, so that a process which serves another family never
imports them; ``LLMEngine`` resolves them by name, as it does all ten
served families (gpt2, llama, nemotron_h, granite_hybrid, deepseek_v2,
falcon_h1, qwen3_next, smallthinker, exaone_moe, keye_vl2;
``serve/llm_engine._model_bundle``):
DeepSeek-V2 (``models/deepseek_v2.py``: latent attention over a latent cache, group-
limited experts), Falcon-H1 (``models/falcon_h1.py``: rotary grouped-query
attention AND a Mamba-2 mixer side by side in every layer, both caches a
layer, fourteen muP multipliers), Qwen3-Next (``models/qwen3_next.py``:
three Gated DeltaNet layers to one gated-attention layer, a float32
delta-rule state beside K/V rings, top-10-of-512 experts and a gated shared
expert in every layer) and SmallThinker (``models/smallthinker.py``: one
global attention layer without a position embedding to three rotary window
layers, window rings beside full rings in one cache, a router read before
attention, gated-ReLU experts all held) and K-EXAONE
(``models/exaone_moe.py``: three rotary window layers of 128 keys to one
global layer without a position embedding, each sublayer's norm after it, a
sigmoid router beside a shared expert, and a multi-token-prediction module
that the engine serves as the model's own draft: a step verifies two rows a
slot and yields one or two tokens) and Keye-VL-2.0's language model
(``models/keye_vl2.py``: grouped-query attention that reads only the keys a
learned indexer picks a query, an exact top-k without a sort, a decode step
that gathers its picks and a chunk masked by its queries' sets, an
indexer-key ring beside the K/V ring, softmax top-8-of-128 experts; text
only). ``models/resnet.py`` is imported by its path too.

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

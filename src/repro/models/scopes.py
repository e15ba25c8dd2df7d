"""Names of the served forward's layers on the device.

Each is a ``jax.named_scope``: it names the operations traced inside it
in the compiled program's ``op_name`` metadata, and so in a profiler
trace, at no cost when the program runs.  Scopes nest; a path reads
from the step down, e.g. ``serve_step/layers/moe/experts``.

Steps (one around each step program's body):
  ``prefill_step``, ``serve_step``  launch steps (``launch/steps.py``)
  ``decode_step``, ``chunk_step``, ``mixed_step``, ``wave_step``
                                    the engine's steps
                                    (``serving/executor.py``)
Model (``models/lm.py``):
  ``embed``      token embedding
  ``layers``     the scan over blocks; its own operations (the scan's
                 per-layer slicing and stacking) are its self time
  ``attention``  norm and attention mixer, cache write included
  ``mamba``      norm and state-space mixer
  ``mlp``        norm and dense FFN
  ``moe``        norm and MoE FFN
  ``head``       final norm, unembedding and the step's argmax
MoE layer (``models/moe.py``):
  ``router``     gating, top-k, the row mask and the expert histogram
  ``route``      METRO's or EPLB's choice of replica slot
  ``dispatch``   the pair buffer and the token gather
  ``experts``    the expert weights' operand layout and the grouped
                 FFN (kernel or matmuls and activation)
  ``combine``    gate scale and scatter-add back to tokens
  ``shared_expert``
  ``collective`` the EP mesh's all-gathers and reductions
Attention (``models/layers.py``, ``models/lm.py``):
  ``kv_write``   the KV cache update
"""

PREFILL_STEP = "prefill_step"
SERVE_STEP = "serve_step"
DECODE_STEP = "decode_step"
CHUNK_STEP = "chunk_step"
MIXED_STEP = "mixed_step"
WAVE_STEP = "wave_step"

EMBED = "embed"
LAYERS = "layers"
ATTENTION = "attention"
MAMBA = "mamba"
MLP = "mlp"
MOE = "moe"
HEAD = "head"

ROUTER = "router"
ROUTE = "route"
DISPATCH = "dispatch"
EXPERTS = "experts"
COMBINE = "combine"
SHARED_EXPERT = "shared_expert"
COLLECTIVE = "collective"

KV_WRITE = "kv_write"

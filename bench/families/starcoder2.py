"""model_type "starcoder2": the plain decoder family of ``decoder.py``,
with LayerNorm, a plain GELU MLP and a sliding window."""
from families.decoder import *  # noqa: F401,F403

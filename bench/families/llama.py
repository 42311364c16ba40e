"""model_type "llama" (DeepSeek LLM's published config.json names it so):
the plain decoder family of ``decoder.py``."""
from families.decoder import *  # noqa: F401,F403

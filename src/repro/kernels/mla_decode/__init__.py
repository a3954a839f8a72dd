"""Absorbed MLA decode attention over a latent slot cache (Pallas)."""
from .kernel import mla_decode_attention  # noqa: F401

from .latent import paged_latent_decode
from .ops import decode_ref, flash_decode, paged_decode_ref, paged_flash_decode
from .ref import paged_latent_ref

__all__ = ["flash_decode", "decode_ref", "paged_flash_decode", "paged_decode_ref",
           "paged_latent_decode", "paged_latent_ref"]

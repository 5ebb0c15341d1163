from repro.kernels.decode_attention.decode_attention import \
    cache_width  # noqa: F401
from repro.kernels.decode_attention.ops import decode_attention  # noqa: F401
from repro.kernels.decode_attention.ref import decode_attention_ref  # noqa: F401

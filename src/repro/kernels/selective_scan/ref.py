"""The kernel's reference: the model's XLA path, the bounded chunked
associative scan of `models/layers.py`, which decode and `use_pallas=False`
run and whose gradient the kernel's backward takes."""
from repro.models.layers import \
    _mamba_ssm_chunked as selective_scan_ref  # noqa: F401

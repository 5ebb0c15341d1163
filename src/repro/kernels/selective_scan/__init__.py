from repro.kernels.selective_scan.ops import selective_scan  # noqa: F401
from repro.kernels.selective_scan.ref import selective_scan_ref  # noqa: F401

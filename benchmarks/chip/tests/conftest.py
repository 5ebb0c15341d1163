"""Tests of the chip benchmark's own code, run on the CPU at small sizes:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/chip

They import the benchmark as `run.py` does (by path) and the program from
`src/`."""
from __future__ import annotations

import pathlib
import sys

import pytest

CHIP = pathlib.Path(__file__).resolve().parents[1]
for p in (CHIP, CHIP.parents[1] / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import run  # noqa: E402


@pytest.fixture(scope="session")
def mods():
    return run.modules()


@pytest.fixture(scope="session")
def kind():
    return run.load_module(CHIP / "kinds" / "serve_static.py",
                           "kind_serve_static")


def tiny(**over) -> dict:
    """A dense decoder of the configurations' form at a size the CPU runs
    in seconds; 16 heads of 32 over 4 kv heads, a window shorter than the
    sequences so that the window mask and the ring are exercised."""
    c = {"name": "tiny", "hidden_size": 256, "intermediate_size": 512,
         "num_attention_heads": 8, "num_key_value_heads": 2,
         "num_hidden_layers": 2, "vocab_size": 1024, "rms_norm_eps": 1e-5,
         "rope_theta": 10000.0, "sliding_window": 40,
         "initializer_range": 0.02}
    c.update(over)
    return c

"""Public entry points of the Pallas kernels.

Every kernel compiles for the TPU unless the caller passes
``interpret=True``, which runs the kernel body in Python (the CPU tests do).
Nothing picks interpret mode on the caller's behalf: on a backend other than
the TPU a compiled call fails at lowering instead of running slowly.
"""
from repro.kernels.flash_attention import flash_attention
from repro.kernels.lstm_cell import lstm_cell
from repro.kernels.rmsnorm import rmsnorm
from repro.kernels.ternary import ternary_decode, ternary_encode

__all__ = ["flash_attention", "lstm_cell", "rmsnorm", "ternary_decode",
           "ternary_encode"]

"""Pallas TPU kernels for the performance-critical compute layers, each
with a pure-jnp ref.py oracle and a jit'd ops.py wrapper. Compiled on a
TPU and interpreted elsewhere (``interpret.pallas_interpret``); the CPU
tests validate them in interpret mode, ``tests/test_chip_compile.py``
compiles them for a described v5e chip."""
from .decode_attention import decode_attention, decode_attention_op
from .flash_attention import flash_attention, flash_attention_op
from .relay_copy import relay_assemble, relay_assemble_op
from .ssd_chunk import ssd_chunk, ssd_op

"""Whole step of a layer-list model: the operations the window's work
needed, counted by the module its configuration names
(``granite_flops``), over the window's seconds times the chip's bf16
peak, read as ``mfu.tput`` reads it."""
from bench import harness

read = harness.reader("mfu.tput")

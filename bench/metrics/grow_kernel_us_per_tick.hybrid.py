"""Allocator kernel in a layer-list model's tick: device time of the
grow transaction's Mosaic kernel inside the fused tick, per tick in
the traced window (device trace), read as
``grow_kernel_us_per_tick.tput`` reads it."""
from bench import harness

read = harness.reader("grow_kernel_us_per_tick.tput")

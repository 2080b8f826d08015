"""Model decode step of a layer-list model (``pattern_stack`` through
``_build_mega``): device time of the fused tick program per run of it
in the traced window (device trace), read as ``tick_ms.tput`` reads
it."""
from bench import harness

read = harness.reader("tick_ms.tput")

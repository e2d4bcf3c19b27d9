"""`step_device_ms.train`, read in the cell whose window holds a save cycle, where
it moves `save_cycle_tokens_per_s`."""

from chipbench import spec

read = spec.metric_reader("step_device_ms.train").read

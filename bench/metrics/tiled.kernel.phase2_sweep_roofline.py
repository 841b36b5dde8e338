"""``kernel.phase2_sweep_roofline`` in the tiled cell: the same reader, under a
name of its own because there it moves ``queries_per_s``, not the
served cells' ``served_queries_per_s``."""

from bench.manifest import metric_reader

read = metric_reader("kernel.phase2_sweep_roofline").read

"""``device.idle_share`` in the tiled cell: the same reader, under a
name of its own because there it moves ``queries_per_s``, not the
served cells' ``served_queries_per_s``."""

from bench.manifest import metric_reader

read = metric_reader("device.idle_share").read

"""The yardstick shared by every cell: spec resolution, load loops, arrival
processes, peaks and least-bytes arithmetic, and the trace reduction."""

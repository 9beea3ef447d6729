"""The tiny copies of configurations that ``bench_tiny`` does not list:
each shrinks only what a CPU run cannot hold."""

import bench_tiny

#: the committed mnist8m-silo4 with only N_i and L shrunk
bench_tiny.TINY.setdefault("mnist8m-silo4", dict(Ni=512, L=64))

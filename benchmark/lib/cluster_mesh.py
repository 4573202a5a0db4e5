"""`BenchCluster` for ONE resolver process over several chips: the resolver
is started through `benchmark.lib.mesh_proc`, whose reply to `reduce` also
carries what a mesh adds to a trace (a plane a chip, the collectives)."""

from __future__ import annotations

import sys

from benchmark.lib.cluster import BenchCluster
from foundationdb_tpu.loadgen.deploy import SocketCluster


class BenchClusterMesh(BenchCluster):
    def _argv(self, p) -> list[str]:
        argv = SocketCluster._argv(self, p)
        if p.role != "resolver":
            return argv
        return [sys.executable, "-m", "benchmark.lib.mesh_proc",
                "--ctl", self.control_dir, "served"] + argv[3:]

"""`BenchCluster` for several resolvers: each resolver process is started
through the benchmark's resolver launcher with a control directory of its
own, `ctl/<index>`, so that each can be asked to trace its own chip and to
report on it (one control thread a process watches one directory)."""

from __future__ import annotations

import os
import sys

from benchmark.lib.cluster import BenchCluster
from foundationdb_tpu.loadgen.deploy import SocketCluster


class BenchClusterNR(BenchCluster):
    def resolver_control_dir(self, index: int) -> str:
        return os.path.join(self.control_dir, str(index))

    def _argv(self, p) -> list[str]:
        argv = SocketCluster._argv(self, p)
        if p.role != "resolver":
            return argv
        return [sys.executable, "-m", "benchmark.lib.resolver_proc",
                "--ctl", self.resolver_control_dir(p.index),
                "served"] + argv[3:]

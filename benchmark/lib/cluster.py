"""The program's own launcher, with the one role that holds the chip started
through the benchmark's resolver launcher (same environment, same spec, same
`ready` line): only that process can trace the chip and read its memory."""

from __future__ import annotations

import os
import sys

from foundationdb_tpu.loadgen.deploy import SocketCluster


class BenchCluster(SocketCluster):
    @property
    def control_dir(self) -> str:
        return os.path.join(self.workdir, "ctl")

    def _argv(self, p) -> list[str]:
        argv = super()._argv(p)
        if p.role != "resolver":
            return argv
        # [python, -m, foundationdb_tpu.server, ...] -> the launcher, which
        # hands the rest to foundationdb_tpu.server.main unchanged.
        return [sys.executable, "-m", "benchmark.lib.resolver_proc",
                "--ctl", self.control_dir, "served"] + argv[3:]

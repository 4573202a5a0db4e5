"""Deployed fdbdr: dr_tool drives DR between two TCP clusters.

replicate → pause → switch resumes from the progress key, drains, locks
the source; the destination then serves every acked commit.
"""

import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = dict(os.environ, JAX_PLATFORMS="cpu")


def cli(spec_path, cmds, tries=30):
    last = None
    for _ in range(tries):
        last = subprocess.run(
            [sys.executable, "-m", "foundationdb_tpu.cli",
             "--cluster", spec_path, "--exec", cmds],
            cwd=REPO, env=ENV, capture_output=True, text=True, timeout=60,
        )
        if last.returncode == 0 and "ERROR" not in last.stdout:
            return last
        time.sleep(1)
    raise AssertionError(f"cli failed: {last.stdout!r} {last.stderr!r}")


def dr(cmd, src, dst, *extra, timeout=180):
    return subprocess.run(
        [sys.executable, "-m", "foundationdb_tpu.dr_tool", cmd,
         "--src", src, "--dst", dst, *extra],
        cwd=REPO, env=ENV, capture_output=True, text=True, timeout=timeout,
    )


def test_deployed_dr_replicate_then_switch(cluster_factory):
    # Two clusters, each one process a role and no ratekeeper.
    src_path, dst_path = (
        cluster_factory(proxies=1, ratekeeper=False).spec_path
        for _ in range(2))
    cli(src_path, "writemode on; set dr/a v1; set dr/b v2")
    r = dr("replicate", src_path, dst_path, "--duration", "8")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "replicating" in r.stdout

    st = dr("status", src_path, dst_path)
    assert st.returncode == 0 and "applied=" in st.stdout

    cli(src_path, "writemode on; set dr/c v3")  # lands post-pause
    sw = dr("switch", src_path, dst_path)
    assert sw.returncode == 0, sw.stdout + sw.stderr
    assert "switched at version" in sw.stdout
    # `switch` must have RESUMED (progress key found, tagging still
    # on), not re-bootstrapped from scratch.
    assert "resumed from 0" not in sw.stdout, sw.stdout

    out = cli(dst_path, "getrange dr/ dr0")
    assert all(v in out.stdout for v in ("v1", "v2", "v3")), out.stdout

    # Source is locked: plain writes fail.
    bad = subprocess.run(
        [sys.executable, "-m", "foundationdb_tpu.cli",
         "--cluster", src_path, "--exec", "writemode on; set dr/x y"],
        cwd=REPO, env=ENV, capture_output=True, text=True, timeout=60,
    )
    assert bad.returncode != 0 or "ERROR" in bad.stdout, bad.stdout

    # abort unlocks the (old) source again.
    ab = dr("abort", src_path, dst_path)
    assert ab.returncode == 0, ab.stdout + ab.stderr
    cli(src_path, "writemode on; set dr/y v4; get dr/y")

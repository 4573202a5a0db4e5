"""Mutual TLS on the deployed transport (reference: flow/TLSConfig).

A CA + one leaf cert are generated per test dir; every process and the
CLI load them through the cluster file's `tls` section. Positive path: a
full cluster speaks TLS end-to-end through the CLI. Negative paths: a
plaintext client cannot complete a handshake, and a client presenting a
certificate from a DIFFERENT CA is rejected (mutual verification).
"""

import datetime
import json
import os
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def make_ca_and_leaf(dirpath, prefix: str):
    """Write {prefix}-ca.pem, {prefix}-cert.pem, {prefix}-key.pem."""
    from cryptography import x509
    from cryptography.hazmat.primitives import hashes, serialization
    from cryptography.hazmat.primitives.asymmetric import ec
    from cryptography.x509.oid import NameOID

    now = datetime.datetime.now(datetime.timezone.utc)

    def name(cn):
        return x509.Name([x509.NameAttribute(NameOID.COMMON_NAME, cn)])

    ca_key = ec.generate_private_key(ec.SECP256R1())
    ca_cert = (
        x509.CertificateBuilder()
        .subject_name(name(f"{prefix}-ca")).issuer_name(name(f"{prefix}-ca"))
        .public_key(ca_key.public_key())
        .serial_number(x509.random_serial_number())
        .not_valid_before(now - datetime.timedelta(minutes=5))
        .not_valid_after(now + datetime.timedelta(days=1))
        .add_extension(x509.BasicConstraints(ca=True, path_length=None),
                       critical=True)
        .sign(ca_key, hashes.SHA256())
    )
    leaf_key = ec.generate_private_key(ec.SECP256R1())
    leaf_cert = (
        x509.CertificateBuilder()
        .subject_name(name(f"{prefix}-proc")).issuer_name(name(f"{prefix}-ca"))
        .public_key(leaf_key.public_key())
        .serial_number(x509.random_serial_number())
        .not_valid_before(now - datetime.timedelta(minutes=5))
        .not_valid_after(now + datetime.timedelta(days=1))
        .sign(ca_key, hashes.SHA256())
    )
    paths = {}
    for nm, data in (
        ("ca", ca_cert.public_bytes(serialization.Encoding.PEM)),
        ("cert", leaf_cert.public_bytes(serialization.Encoding.PEM)),
        ("key", leaf_key.private_bytes(
            serialization.Encoding.PEM,
            serialization.PrivateFormat.PKCS8,
            serialization.NoEncryption())),
    ):
        p = os.path.join(dirpath, f"{prefix}-{nm}.pem")
        with open(p, "wb") as f:
            f.write(data)
        paths[nm] = p
    return paths


@pytest.fixture
def tls_cluster(cluster_factory, tmp_path):
    """A sequencer, a resolver, a tlog, two storages and a proxy that all
    load one CA's leaf; yields (spec, spec path, the certificates' dir)."""
    certs = make_ca_and_leaf(str(tmp_path), "main")
    c = cluster_factory(proxies=1, storages=2, ratekeeper=False,
                        spec_extra={"tls": {"cert": certs["cert"],
                                            "key": certs["key"],
                                            "ca": certs["ca"]}})
    return c.spec, c.spec_path, str(tmp_path)


def run_cli(spec_path: str, cmds: str):
    return subprocess.run(
        [sys.executable, "-m", "foundationdb_tpu.cli",
         "--cluster", spec_path, "--exec", cmds],
        cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=60,
    )


class TestTLS:
    def test_tls_cluster_end_to_end(self, tls_cluster):
        _spec, spec_path, _tmp = tls_cluster
        last = None
        for _ in range(30):
            last = run_cli(spec_path, "writemode on; set tls/a v1; get tls/a")
            if last.returncode == 0 and "v1" in last.stdout:
                return
            time.sleep(1)
        raise AssertionError(f"TLS cli failed: {last.stdout} {last.stderr}")

    def test_plaintext_client_rejected(self, tls_cluster):
        spec, spec_path, tmp = tls_cluster
        # A spec WITHOUT the tls section = plaintext transport.
        plain = {k: v for k, v in spec.items() if k != "tls"}
        plain_path = os.path.join(tmp, "plain.json")
        with open(plain_path, "w") as f:
            json.dump(plain, f)
        r = run_cli(plain_path, "getversion")
        assert r.returncode != 0 or "ERROR" in r.stdout, r.stdout

    def test_wrong_ca_client_rejected(self, tls_cluster):
        spec, spec_path, tmp = tls_cluster
        rogue = make_ca_and_leaf(tmp, "rogue")
        bad = dict(spec)
        bad["tls"] = {"cert": rogue["cert"], "key": rogue["key"],
                      "ca": rogue["ca"]}
        bad_path = os.path.join(tmp, "rogue.json")
        with open(bad_path, "w") as f:
            json.dump(bad, f)
        r = run_cli(bad_path, "getversion")
        assert r.returncode != 0 or "ERROR" in r.stdout, r.stdout


class TestNativeClientTLS:
    def test_c_client_speaks_tls(self, tls_cluster):
        """The native C client completes the mutual handshake (dlopen'd
        OpenSSL 3) and drives GRV/commit/read against a TLS cluster —
        closing the r4 gap where a TLS cluster was unreachable from C.
        Wrong-CA and plaintext C connections are rejected."""
        from foundationdb_tpu.client.net_client import NetClient
        from foundationdb_tpu.core.mutations import Mutation, MutationType
        from foundationdb_tpu.core.types import single_key_range

        spec, spec_path, tmp = tls_cluster
        host, port = spec["proxy"][0].rsplit(":", 1)
        tls = spec["tls"]

        c = None
        for _ in range(30):
            try:
                c = NetClient(host, int(port), tls=tls)
                break
            except ConnectionError:
                time.sleep(1)
        assert c is not None, "C client never completed the TLS handshake"
        rv = c.get_read_version()
        assert rv >= 0
        cv = c.commit(
            rv,
            [Mutation(MutationType.SET_VALUE, b"ctls/k", b"v")],
            write_ranges=[single_key_range(b"ctls/k")],
        )
        assert cv > rv
        # Read through the same TLS connection (storage routed service).
        rv2 = c.get_read_version()
        assert c.get(b"ctls/k", rv2) == b"v"
        c.close()

        # Wrong CA: the handshake must fail, not fall back.
        rogue = make_ca_and_leaf(tmp, "csiderogue")
        with pytest.raises(ConnectionError):
            NetClient(host, int(port),
                      tls={"cert": rogue["cert"], "key": rogue["key"],
                           "ca": rogue["ca"]})

        # Plaintext C client against the TLS port: first call fails.
        from foundationdb_tpu.core.errors import FdbError as _FdbError
        try:
            pc = NetClient(host, int(port))
        except ConnectionError:
            return  # refused at connect — also fine
        with pytest.raises(_FdbError):
            pc.get_read_version()
        pc.close()

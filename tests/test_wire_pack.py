"""Wire-format production packer (native/keypack.cpp) parity.

The C packer must match the Python object path bit-for-bit: same padded
tensors out of _pack_wire as _pack, and identical verdicts from
resolve_wire as resolve, across truncation, transactions with more ranges
than a row has slots (continuation rows, cut into dispatches at the same
transaction by both), and empty-range edge cases (mirrors the reference's
requirement that the serialized ResolveTransactionBatchRequest round-trips
losslessly)."""

import numpy as np
import pytest

from foundationdb_tpu.core.types import KeyRange, TxnConflictInfo
from foundationdb_tpu.models.conflict_set import (
    TPUConflictSet,
    encode_resolve_batch,
)


def random_txns(rng, n, max_key=24, overlong=False, many_ranges=False):
    txns = []
    for _ in range(n):
        def key():
            ln = rng.integers(0, max_key + (16 if overlong else 0))
            return bytes(rng.integers(0, 256, ln, dtype=np.uint8))

        def krange():
            a, b = key(), key()
            if rng.random() < 0.3:
                return KeyRange(a, a + b"\x00")  # point range
            return KeyRange(min(a, b), max(a, b))  # may be empty when a == b

        n_r = int(rng.integers(0, 12 if many_ranges else 3))
        n_w = int(rng.integers(0, 12 if many_ranges else 3))
        txns.append(TxnConflictInfo(
            read_version=int(rng.integers(0, 50)),
            read_ranges=[krange() for _ in range(n_r)],
            write_ranges=[krange() for _ in range(n_w)],
        ))
    return txns


def make_pair(**kw):
    kw.setdefault("capacity", 1 << 10)
    kw.setdefault("batch_size", 64)
    kw.setdefault("max_read_ranges", 4)
    kw.setdefault("max_write_ranges", 4)
    kw.setdefault("max_key_bytes", 16)
    return TPUConflictSet(**kw), TPUConflictSet(**kw)


class TestWirePackParity:
    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_tensors_identical(self, seed):
        rng = np.random.default_rng(seed)
        obj, wirecs = make_pair()
        obj.base_version = wirecs.base_version = 0
        # Up to 11 ranges of a kind on 4 slots: most transactions take
        # two or three rows, so the 64 go out in several dispatches.
        txns = random_txns(rng, 64, overlong=True, many_ranges=True)
        buf = np.frombuffer(encode_resolve_batch(txns), np.uint8)
        chunks = obj._chunks(txns)
        assert len(chunks) > 1
        off, left = 0, len(txns)
        for lo, hi in chunks:
            bt_obj = obj._pack(txns[lo:hi])
            bt_wire, off, taken = wirecs._pack_wire(
                buf, off, min(left, wirecs.batch_size))
            assert taken == hi - lo
            left -= taken
            assert bt_obj.cont is not None and bt_obj.cont.any()
            for name in bt_obj._fields:
                a, b = getattr(bt_obj, name), getattr(bt_wire, name)
                assert np.array_equal(np.asarray(a), np.asarray(b)), name
        assert (off, left) == (buf.size, 0)

    @pytest.mark.parametrize("seed", [3, 4])
    def test_verdicts_identical_over_stream(self, seed):
        rng = np.random.default_rng(seed)
        obj, wirecs = make_pair()
        for cv in range(1, 6):
            txns = random_txns(rng, 100, overlong=(cv % 2 == 0),
                               many_ranges=(cv % 2 == 1))
            v1 = obj.resolve(txns, commit_version=cv * 10)
            v2 = wirecs.resolve_wire(
                encode_resolve_batch(txns), commit_version=cv * 10
            )
            assert v1 == v2

    def test_count_txns(self):
        rng = np.random.default_rng(9)
        txns = random_txns(rng, 37)
        from foundationdb_tpu.models.conflict_set import _keypack_lib, _u8

        buf = np.frombuffer(encode_resolve_batch(txns), np.uint8)
        lib = _keypack_lib()
        assert lib.kp_count_txns(_u8(buf), buf.size, 0) == 37

    def test_malformed_wire_raises(self):
        cs, _ = make_pair()
        with pytest.raises(ValueError):
            cs.resolve_wire(b"\x01\x02\x03", commit_version=10)

    def test_truncation_all_ff_end(self):
        """An overlong range end whose prefix is all 0xff packs to +inf."""
        obj, wirecs = make_pair()
        obj.base_version = wirecs.base_version = 0
        txns = [TxnConflictInfo(
            read_version=0,
            read_ranges=[KeyRange(b"\x01", b"\xff" * 40)],
            write_ranges=[KeyRange(b"\xff" * 40, b"\xff" * 41)],
        )]
        bt_obj = obj._pack(txns)
        buf = np.frombuffer(encode_resolve_batch(txns), np.uint8)
        bt_wire, _off, taken = wirecs._pack_wire(buf, 0, 1)
        assert taken == 1 and bt_obj.cont is None and bt_wire.cont is None
        for name in bt_obj._fields[:-1]:
            assert np.array_equal(
                np.asarray(getattr(bt_obj, name)),
                np.asarray(getattr(bt_wire, name))), name

    def test_async_pipelining_matches_sync(self):
        rng = np.random.default_rng(11)
        a, b = make_pair()
        txns1 = random_txns(rng, 80)
        txns2 = random_txns(rng, 80)
        c1 = a.resolve_async(txns1, 10)
        c2 = a.resolve_async(txns2, 20)  # dispatched before collecting c1
        assert [c1(), c2()] == [b.resolve(txns1, 10), b.resolve(txns2, 20)]


class TestWireStructCrossVersion:
    """Trace-context fields on the RPC structs (obs subsystem) follow the
    established shorter-forms convention: peers predating a field parse
    the shorter tuple cleanly, and the NEW packer emits the short form
    whenever the field is unset — so an old peer never even sees the
    longer tuple unless a tracing (new) client asked for it."""

    def _entry(self, sid):
        from foundationdb_tpu.runtime import wire

        return wire._STRUCTS[sid]

    def test_commit_request_trace_round_trip(self):
        from foundationdb_tpu.runtime import wire
        from foundationdb_tpu.runtime.commit_proxy import CommitRequest

        req = CommitRequest(read_version=7, trace=0xBEEF)
        out = wire.loads(wire.dumps(req))
        assert out.trace == 0xBEEF and out.read_version == 7

    def test_unsampled_request_packs_the_short_form(self):
        from foundationdb_tpu.runtime.commit_proxy import CommitRequest

        _cls, to_tuple, from_tuple = self._entry(5)
        fields = to_tuple(CommitRequest(read_version=7))
        assert len(fields) == 10  # no trailing trace field on the wire
        assert from_tuple(fields).trace is None

    def test_old_peer_short_forms_parse_cleanly(self):
        _cls, _to, from_tuple = self._entry(5)
        # A peer predating lock_aware/.../trace sent only 5 fields.
        old = from_tuple((3, [], [], [], False))
        assert old.trace is None and old.priority == "default"
        assert old.admission_attempts == 0
        # A peer predating ONLY trace sent 10.
        mid = from_tuple((3, [], [], [], False, True, None, "batch",
                          False, 2))
        assert mid.trace is None and mid.lock_aware is True
        assert mid.priority == "batch" and mid.admission_attempts == 2

    def test_commit_result_spans_cross_version(self):
        from foundationdb_tpu.runtime import wire
        from foundationdb_tpu.runtime.commit_proxy import CommitResult

        _cls, to_tuple, from_tuple = self._entry(6)
        # Unsampled: 2-field form on the wire (old peers parse it).
        assert len(to_tuple(CommitResult(10, 3))) == 2
        assert from_tuple((10, 3)).spans is None
        # Sampled: spans round-trip through the full codec.
        spans = (("proxy_admit", 0.001, 0.002),
                 ("proxy_total", 0.001, 0.009))
        out = wire.loads(wire.dumps(CommitResult(10, 3, spans)))
        assert out.version == 10 and out.batch_order == 3
        assert out.spans == spans


class TestHostileWire:
    """The C parser is the RPC trust boundary: hostile counts/lengths must
    be rejected, never overflow into misparses or out-of-bounds reads."""

    def _lib(self):
        from foundationdb_tpu.models.conflict_set import _keypack_lib

        return _keypack_lib()

    def test_huge_range_counts_rejected(self):
        import struct

        from foundationdb_tpu.models.conflict_set import _u8

        # n_reads + n_writes would overflow int32 if summed naively.
        blob = struct.pack("<qii", 0, 2**30, 2**30)
        buf = np.frombuffer(blob, np.uint8)
        assert self._lib().kp_count_txns(_u8(buf), buf.size, 0) == -1

    def test_huge_key_lengths_rejected(self):
        import struct

        from foundationdb_tpu.models.conflict_set import _u8

        # bl + el would wrap negative in 32-bit arithmetic.
        blob = struct.pack("<qii", 0, 1, 0) + struct.pack(
            "<ii", 0x7FFFFFFF, 0x7FFFFFFF
        )
        buf = np.frombuffer(blob, np.uint8)
        assert self._lib().kp_count_txns(_u8(buf), buf.size, 0) == -1

    def test_count_beyond_buffer_rejected_before_dispatch(self):
        cs, _ = make_pair()
        txns = random_txns(np.random.default_rng(5), 10)
        wire = encode_resolve_batch(txns)
        state_before = cs.state
        with pytest.raises(ValueError):
            cs.resolve_wire(wire, commit_version=10, count=11)
        # Nothing dispatched: device history untouched, version not burned.
        assert cs.state is state_before
        assert cs._last_commit == 0
        assert cs.resolve_wire(wire, commit_version=10, count=10)

    def test_far_future_read_version_rejected(self):
        from foundationdb_tpu.core.types import TxnConflictInfo

        cs, _ = make_pair()
        t = TxnConflictInfo(
            read_version=2**40,
            read_ranges=[KeyRange(b"a", b"b")],
            write_ranges=[],
        )
        with pytest.raises(ValueError):
            cs.resolve_wire(encode_resolve_batch([t]), commit_version=10)

// Production batch packer: resolver wire format -> padded device tensors.
//
// The reference resolver receives ResolveTransactionBatchRequest as flat
// serialized bytes and walks them in C++ (fdbserver/Resolver.actor.cpp +
// ConflictSet.h ConflictBatch::addTransaction). This is the TPU-native
// equivalent: one C pass over the batch blob emits the padded int32 key
// tensors models/conflict_kernel.py consumes, so the Python runtime never
// touches per-transaction objects on the hot path.
//
// Wire format (little-endian, packed tight):
//   per txn:
//     int64  read_version (absolute)
//     int32  n_reads
//     int32  n_writes
//     then n_reads + n_writes ranges (reads first):
//       int32 begin_len, int32 end_len, begin bytes, end bytes
//
// Key packing must match core/keypack.py KeyCodec bit-for-bit: big-endian
// bytes into int32 words, XOR 0x80000000 bias, trailing length column;
// overlong begins truncate down, overlong ends round up to the prefix
// successor (all-0xff prefix -> +inf sentinel).
//
// Row layout must match models/conflict_set.py _pack bit-for-bit: a
// transaction's non-empty ranges fill slots in wire order; one with more
// than r_cap reads or q_cap writes runs on into CONTINUATION rows right
// after its first (same read version, txn_mask set, `cont` set): range c
// lands in row c / cap, slot c % cap, which in a row-major [B, cap, W]
// tensor is simply slot c counted from the transaction's first row. No
// range is ever widened, merged or dropped, and a transaction is never
// split across two batches: the pass stops before one that does not fit.

#include <algorithm>
#include <cstdint>
#include <cstring>
#include <vector>

namespace {

constexpr int32_t INT32_MAX_V = 0x7fffffff;
constexpr int MAX_KEY_BYTES = 256;  // packer scratch bound (codec max)

struct RangeView {
  const uint8_t* b;
  int32_t bl;
  const uint8_t* e;
  int32_t el;
};

int bytecmp(const uint8_t* a, int la, const uint8_t* b, int lb) {
  int n = la < lb ? la : lb;
  int c = std::memcmp(a, b, n);
  if (c) return c;
  return la - lb;
}

// Pack one key into out[0..n_words]: words + length column.
void pack_key(const uint8_t* k, int len, int n_words, bool end_mode,
              int32_t* out) {
  uint8_t tmp[MAX_KEY_BYTES];
  const int maxb = 4 * n_words;
  if (len > maxb) {
    if (end_mode) {
      // Successor of the truncated prefix: drop trailing 0xff, bump last.
      std::memcpy(tmp, k, maxb);
      int i = maxb - 1;
      while (i >= 0 && tmp[i] == 0xff) --i;
      if (i < 0) {  // all-0xff prefix: no successor -> +inf sentinel
        for (int w = 0; w <= n_words; ++w) out[w] = INT32_MAX_V;
        return;
      }
      ++tmp[i];
      len = i + 1;
      k = tmp;
    } else {
      len = maxb;  // begins truncate down
    }
  }
  for (int w = 0; w < n_words; ++w) {
    uint32_t word = 0;
    for (int b = 0; b < 4; ++b) {
      const int idx = 4 * w + b;
      word = (word << 8) | (idx < len ? k[idx] : 0u);
    }
    out[w] = static_cast<int32_t>(word ^ 0x80000000u);
  }
  out[n_words] = len;
}

// Emit `live` into consecutive slots of row-major [*, cap, W] tensors,
// starting at the transaction's first row: past slot cap - 1 they are the
// slots of its continuation rows (the caller made sure the rows exist).
void emit_ranges(const std::vector<RangeView>& live, int n_words,
                 int32_t* begin_out, int32_t* end_out, uint8_t* mask_out) {
  const int w = n_words + 1;
  for (size_t c = 0; c < live.size(); ++c) {
    pack_key(live[c].b, live[c].bl, n_words, false, begin_out + c * w);
    pack_key(live[c].e, live[c].el, n_words, true, end_out + c * w);
    mask_out[c] = 1;
  }
}

}  // namespace

extern "C" {

// Walks up to `count` transactions starting at byte `offset`; fills the
// padded batch tensors (callers pass zero/INT32_MAX-prefilled arrays of
// shape B x R x W / B x Q x W / B x R / B x Q / B). It stops early, before
// a transaction whose rows no longer fit in b_cap. used[0] = transactions
// taken, used[1] = rows filled; cont[row] = 1 on continuation rows. Returns
// the wire offset just past the last transaction taken; -1 on malformed
// input, overrun, or a transaction that b_cap rows cannot hold; -2 where a
// transaction needs a continuation row and the caller takes none (`cont`
// null: the window path, one row a transaction).
int64_t kp_pack_batch(
    const uint8_t* wire, int64_t wire_len, int64_t offset, int count,
    int b_cap, int r_cap, int q_cap, int n_words, int64_t base_version,
    int32_t* read_begin, int32_t* read_end, uint8_t* read_mask,
    int32_t* write_begin, int32_t* write_end, uint8_t* write_mask,
    int32_t* read_version, uint8_t* txn_mask, uint8_t* cont,
    int32_t* used) {
  const int w = n_words + 1;
  if (count > b_cap || r_cap <= 0 || q_cap <= 0) return -1;
  // pack_key's truncation scratch is MAX_KEY_BYTES — a wider codec would
  // smash the stack on overlong wire keys. Reject the config, not the key.
  if (n_words <= 0 || 4 * n_words > MAX_KEY_BYTES) return -1;
  std::vector<RangeView> reads, writes;
  int t = 0;
  int64_t row = 0;
  for (; t < count; ++t) {
    const int64_t txn_offset = offset;
    if (offset + 16 > wire_len) return -1;
    int64_t rv;
    int32_t n_reads, n_writes;
    std::memcpy(&rv, wire + offset, 8);
    std::memcpy(&n_reads, wire + offset + 8, 4);
    std::memcpy(&n_writes, wire + offset + 12, 4);
    offset += 16;
    if (n_reads < 0 || n_writes < 0) return -1;
    // All arithmetic below in int64: hostile 32-bit counts/lengths must
    // not overflow int before the bounds checks run (this parser is the
    // RPC trust boundary).
    const int64_t n_ranges = static_cast<int64_t>(n_reads) + n_writes;

    reads.clear();
    writes.clear();
    for (int64_t i = 0; i < n_ranges; ++i) {
      if (offset + 8 > wire_len) return -1;
      int32_t bl, el;
      std::memcpy(&bl, wire + offset, 4);
      std::memcpy(&el, wire + offset + 4, 4);
      offset += 8;
      if (bl < 0 || el < 0 ||
          static_cast<int64_t>(bl) + el > wire_len - offset)
        return -1;
      RangeView v{wire + offset, bl, wire + offset + bl, el};
      offset += static_cast<int64_t>(bl) + el;
      if (bytecmp(v.b, v.bl, v.e, v.el) < 0)  // drop empty ranges
        (i < n_reads ? reads : writes).push_back(v);
    }

    // Relative read version, clamped like _rel_read (ancient readers -> -1,
    // strictly below every window floor -> TOO_OLD). A version beyond int32
    // is rejected: the Python object path raises on the same input, and a
    // silent wrap would turn a far-future reader into a recent one.
    const int64_t rel = rv - base_version;
    if (rel > 0x7fffffffLL) return -1;
    const int64_t need = std::max<int64_t>(
        1, std::max((static_cast<int64_t>(reads.size()) + r_cap - 1) / r_cap,
                    (static_cast<int64_t>(writes.size()) + q_cap - 1) / q_cap));
    if (need > 1 && !cont) return -2;
    if (need > b_cap) return -1;
    if (row + need > b_cap) {  // the next batch's first transaction
      offset = txn_offset;
      break;
    }
    for (int64_t k = 0; k < need; ++k) {
      txn_mask[row + k] = 1;
      read_version[row + k] = static_cast<int32_t>(rel < -1 ? -1 : rel);
      if (k) cont[row + k] = 1;
    }
    emit_ranges(reads, n_words, read_begin + row * r_cap * w,
                read_end + row * r_cap * w, read_mask + row * r_cap);
    emit_ranges(writes, n_words, write_begin + row * q_cap * w,
                write_end + row * q_cap * w, write_mask + row * q_cap);
    row += need;
  }
  if (used) {
    used[0] = t;
    used[1] = static_cast<int32_t>(row);
  }
  return offset;
}

// Count (and structurally validate) the transactions in [offset, wire_len).
int64_t kp_count_txns(const uint8_t* wire, int64_t wire_len, int64_t offset) {
  int64_t n = 0;
  while (offset < wire_len) {
    if (offset + 16 > wire_len) return -1;
    int32_t n_reads, n_writes;
    std::memcpy(&n_reads, wire + offset + 8, 4);
    std::memcpy(&n_writes, wire + offset + 12, 4);
    offset += 16;
    if (n_reads < 0 || n_writes < 0) return -1;
    const int64_t n_ranges = static_cast<int64_t>(n_reads) + n_writes;
    for (int64_t i = 0; i < n_ranges; ++i) {
      if (offset + 8 > wire_len) return -1;
      int32_t bl, el;
      std::memcpy(&bl, wire + offset, 4);
      std::memcpy(&el, wire + offset + 4, 4);
      offset += 8;
      if (bl < 0 || el < 0 ||
          static_cast<int64_t>(bl) + el > wire_len - offset)
        return -1;
      offset += static_cast<int64_t>(bl) + el;
    }
    ++n;
  }
  return n;
}

}  // extern "C"

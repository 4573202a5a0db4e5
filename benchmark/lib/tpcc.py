"""TPC-C's transaction mix as one resolver sees it: the conflict ranges of
new-order, payment and delivery over an ordered key-value store, dealt from
shuffled decks of cards.

TPC Benchmark C, revision 5.11 (quoted from memory; this sandbox has no
network): the profiles of clauses 2.4-2.8, the mix of clause 5.2.3 as every
public run deals it (45 / 43 / 4 / 4 / 4 of a deck of 100 cards, clause
5.2.4.2), ids by NURand (clause 2.1.6). How each profile reads and writes a
key-value store is upstream's (fdbserver/workloads/TPCC.actor.cpp: point
gets, point sets and clears, `getRange` over a key prefix). Order-status and
stock-level are read-only and a rolled-back new-order never commits: none
of them reaches a resolver, so their cards are dealt, counted and dropped.

The keys (`assumed`; the configuration file says why): one table byte, then
big-endian fixed-width ids, so byte order is id order and a prefix is a
range.

    W w4                warehouse           I i4              item
    D w4 d1             district            S w4 i4           stock
    C w4 d1 c2          customer            O w4 d1 o4        order
    L w4 d1 l2 c2       customer by name    N w4 d1 o4        new-order
    H w4 d1 c2 seq8     history             P w4 d1 o4 ol1    order line

Made for steadiness: every deck holds the mix exactly, `ol_cnt` takes each
of 5..15 as evenly as 45 new-orders allow, and the shares that the clauses
give as percentages (by last name, remote customer, remote order line,
rolled back) are dealt by quota a deck, not by coin: rows, ranges and
dispatches a batch are near-constants and the seed moves ids alone.
Everything drawn from the seed is drawn when the stream is made; dealing a
batch draws nothing.

The generator keeps two counters a district, as the database would: the
next order number (3,001 after the load, clause 4.3.3.1) and the oldest
undelivered order (2,101: orders 2,101-3,000 are loaded undelivered). A
transaction that conflicts is not retried, and the counters advance as if
it had committed: the stream is built ahead of its verdicts.
"""

from __future__ import annotations

import struct

import numpy as np

DISTRICTS = 10
CUSTOMERS = 3000  # a district
ITEMS = 100_000
LAST_NAMES = 1000
FIRST_NEW_ORDER = 3001  # a district's next order number after the load
FIRST_UNDELIVERED = 2101
OL_MIN, OL_MAX = 5, 15
# The deck of clause 5.2.4.2: what is dealt, and what a resolver is sent.
NEW_ORDER, PAYMENT, DELIVERY, READ_ONLY = 0, 1, 2, 3
DECK = (NEW_ORDER,) * 45 + (PAYMENT,) * 43 + (DELIVERY,) * 4 + (READ_ONLY,) * 8
# Clause 2.1.6's A constants, and the shares the profiles give.
A_CUSTOMER, A_ITEM, A_LAST = 1023, 8191, 255
BY_LAST_NAME = 0.60      # of payments (clause 2.5.1.2)
REMOTE_CUSTOMER = 0.15   # of payments
REMOTE_LINE = 0.01       # of order lines (clause 2.4.1.5)
ROLLED_BACK = 0.01       # of new-orders (clause 2.4.1.4)
# The prefixes this stream range-reads, by table byte: a district's
# new-order keys, an order's lines, a last name's index entries.
PREFIX_LEN = {ord("N"): 6, ord("P"): 10, ord("L"): 8}

_W = struct.Struct(">cI").pack
_WD = struct.Struct(">cIB").pack
_WDC = struct.Struct(">cIBH").pack
_WDO = struct.Struct(">cIBI").pack
_WDOL = struct.Struct(">cIBIB").pack
_WI = struct.Struct(">cII").pack
_HIST = struct.Struct(">cIBHQ").pack


def strinc(prefix: bytes) -> bytes:
    """The first key that `prefix` does not begin (no prefix here ends in
    0xff bytes only)."""
    stripped = prefix.rstrip(b"\xff")
    return stripped[:-1] + bytes([stripped[-1] + 1])


def point(key: bytes) -> tuple:
    return (key, key + b"\x00")


def nurand(rng, a: int, x: int, y: int, c: int, size) -> np.ndarray:
    """Clause 2.1.6: (((random(0, A) | random(x, y)) + C) % (y - x + 1)) + x."""
    return ((rng.integers(0, a + 1, size) | rng.integers(x, y + 1, size))
            + c) % (y - x + 1) + x


def quota(deck: np.ndarray, a_deck: int, share: float) -> np.ndarray:
    """How many of deck k's `a_deck` events take a `share`: the whole part
    of the running total, so every deck is within one of the share and any
    run of decks within one of it too."""
    return (np.floor((deck + 1) * a_deck * share)
            - np.floor(deck * a_deck * share)).astype(np.int64)


def _marks(rng, n: int, a_deck: int, share: float) -> np.ndarray:
    """Which of `n` events, `a_deck` a deck and in deck order, take a
    `share`: quota() of each deck's, at places the seed chooses."""
    n_decks = -(-n // a_deck)
    want = quota(np.arange(n_decks), a_deck, share)
    place = rng.permuted(np.tile(np.arange(a_deck), (n_decks, 1)), axis=1)
    return (place < want[:, None]).reshape(-1)[:n]


def load_last_names() -> np.ndarray:
    """The middle customer of each last name in a district, as payment by
    name chooses it (clause 2.5.2.2: the ceil(n/2)-th by first name; here
    by id). The load gives customers 1..1,000 the names 0..999 in turn and
    the other 2,000 NURand(255, 0, 999) names (clause 4.3.3.1); `assumed`:
    the same draw in every district, from a constant of its own."""
    rng = np.random.default_rng(4_3_3_1)
    names = np.concatenate([np.arange(LAST_NAMES), nurand(
        rng, A_LAST, 0, LAST_NAMES - 1, 157, CUSTOMERS - LAST_NAMES)])
    middle = np.zeros(LAST_NAMES, np.int64)
    for name in range(LAST_NAMES):
        ids = np.flatnonzero(names == name) + 1
        middle[name] = ids[(len(ids) + 1) // 2 - 1]
    return middle


def loaded_order(w: int, d: int, o: int) -> tuple:
    """(ol_cnt, customer) of an order the load made (clause 4.3.3.1 draws
    both at random; here a fixed scramble of the order's id, the same in
    every run)."""
    h = (w * 2_654_435_761 + d * 40_503 + o * 2_246_822_519) & 0xFFFFFFFF
    h ^= h >> 15
    return OL_MIN + h % (OL_MAX - OL_MIN + 1), 1 + (h >> 8) % CUSTOMERS


class Deal:
    """The transactions of one run that reach a resolver, in order, made
    from the seed: `n_txns` of them, the cards dropped on the way counted.
    `batch(n, size)` deals transactions [n * size, (n + 1) * size) as
    `(kind, reads, writes)` with lists of `(begin, end)` byte pairs; it
    must be called for n = 0, 1, 2, ... in turn, because the districts'
    order counters move with it (`replay()` gives a fresh deal of the same
    stream for a second pass)."""

    def __init__(self, warehouses: int, seeds: list, n_txns: int):
        self.warehouses = warehouses
        self.n_txns = n_txns
        rng = np.random.default_rng(list(seeds))
        sent_a_deck = DECK.count(NEW_ORDER) + DECK.count(PAYMENT) \
            + DECK.count(DELIVERY)
        n_decks = -(-n_txns // (sent_a_deck - 1)) + 1
        cards = rng.permuted(np.tile(np.array(DECK, np.int8), (n_decks, 1)),
                             axis=1).reshape(-1)
        # New-orders, in deck order: ol_cnt as evenly as 45 a deck allow
        # (four of each of the eleven counts and one more, taken in turn),
        # one in a hundred rolled back, 1 % of the lines remote.
        is_no = cards == NEW_ORDER
        a = DECK.count(NEW_ORDER)
        n_counts = OL_MAX - OL_MIN + 1
        base = np.arange(a - a % n_counts) % n_counts
        extra = (np.arange(n_decks)[:, None] * (a % n_counts)
                 + np.arange(a % n_counts)[None, :]) % n_counts
        ol_cnt = OL_MIN + rng.permuted(np.concatenate(
            [np.tile(base, (n_decks, 1)), extra], axis=1), axis=1).reshape(-1)
        rolled_back = _marks(rng, n_decks * a, a, ROLLED_BACK)
        is_rolled_back = np.zeros(len(cards), bool)
        is_rolled_back[np.flatnonzero(is_no)[rolled_back]] = True
        # What is sent, cut to n_txns, and the cards dropped before each.
        sent_at = np.flatnonzero(
            (cards != READ_ONLY) & ~is_rolled_back)[:n_txns]
        self.kinds = cards[sent_at]
        self._read_only_before = np.cumsum(cards == READ_ONLY)[sent_at]
        self._rolled_back_before = np.cumsum(is_rolled_back)[sent_at]
        n_no = int(np.count_nonzero(self.kinds == NEW_ORDER))
        self.ol_cnt = ol_cnt[~rolled_back][:n_no]
        n_pay = int(np.count_nonzero(self.kinds == PAYMENT))
        b = DECK.count(PAYMENT)
        self.by_name = _marks(rng, n_pay, b, BY_LAST_NAME)
        self.remote_customer = _marks(rng, n_pay, b, REMOTE_CUSTOMER)
        # Ids: the run's C constants (clause 2.1.6.1), then every draw.
        c_id, c_item, c_last = (int(rng.integers(0, k + 1)) for k in (
            A_CUSTOMER, A_ITEM, A_LAST))
        n = n_txns
        self.w = rng.integers(1, warehouses + 1, n)
        self.d = rng.integers(1, DISTRICTS + 1, n)
        self.c = nurand(rng, A_CUSTOMER, 1, CUSTOMERS, c_id, n)
        self.items = nurand(rng, A_ITEM, 1, ITEMS, c_item, (n_no, OL_MAX))
        self.remote_line = np.zeros((n_no, OL_MAX), bool)
        self.remote_line[np.arange(OL_MAX)[None, :]
                         < self.ol_cnt[:, None]] = _marks(
            rng, int(self.ol_cnt.sum()), a * (OL_MIN + OL_MAX) // 2,
            REMOTE_LINE)
        # Another warehouse than w, uniformly (w itself where there is
        # one warehouse): for remote lines and remote customers.
        self.other_w = (self.w - 1 + rng.integers(
            1, max(2, warehouses), n)) % warehouses + 1
        self.other_d = rng.integers(1, DISTRICTS + 1, n)
        self.last = nurand(rng, A_LAST, 0, LAST_NAMES - 1, c_last, n_pay)
        self._middle = load_last_names()
        self._start()

    def _start(self) -> None:
        self._at = self._no = self._pay = 0
        self.next_order: dict = {}   # (w, d) -> next order number
        self.undelivered: dict = {}  # (w, d) -> oldest undelivered order
        self.orders: dict = {}       # (w, d, o) -> (ol_cnt, customer)
        self.true_ranges = 0
        self.ranges = 0
        self.longest_key = 0

    def dropped(self) -> dict:
        """The cards dealt and dropped on the way to the last transaction
        dealt so far: read-only profiles and rolled-back new-orders."""
        if not self._at:
            return {"read_only_dropped": 0, "rolled_back_dropped": 0}
        return {
            "read_only_dropped": int(self._read_only_before[self._at - 1]),
            "rolled_back_dropped": int(
                self._rolled_back_before[self._at - 1])}

    def replay(self) -> "Deal":
        """The same stream from its first transaction, for a second pass;
        shares what was drawn, draws nothing."""
        other = object.__new__(Deal)
        other.__dict__.update(self.__dict__)
        other._start()
        return other

    # -- the three profiles ---------------------------------------------------

    def _new_order(self, t: int) -> tuple:
        k = self._no
        self._no += 1
        w, d, c = int(self.w[t]), int(self.d[t]), int(self.c[t])
        n = int(self.ol_cnt[k])
        o = self.next_order.get((w, d), FIRST_NEW_ORDER)
        self.next_order[(w, d)] = o + 1
        self.orders[(w, d, o)] = (n, c)
        other = int(self.other_w[t])
        stocks = [_WI(b"S", other if far else w, i) for i, far in zip(
            self.items[k, :n].tolist(), self.remote_line[k, :n].tolist())]
        district = _WD(b"D", w, d)
        reads = [_W(b"W", w), district, _WDC(b"C", w, d, c)]
        reads += [_W(b"I", i) for i in self.items[k, :n].tolist()]
        reads += stocks
        writes = [district] + stocks + [_WDO(b"O", w, d, o),
                                        _WDO(b"N", w, d, o)]
        writes += [_WDOL(b"P", w, d, o, ol) for ol in range(1, n + 1)]
        return [point(x) for x in reads], [point(x) for x in writes]

    def _payment(self, t: int) -> tuple:
        k = self._pay
        self._pay += 1
        w, d = int(self.w[t]), int(self.d[t])
        cw, cd = (int(self.other_w[t]), int(self.other_d[t])) \
            if self.remote_customer[k] else (w, d)
        reads = []
        if self.by_name[k]:
            name = int(self.last[k])
            prefix = _WDC(b"L", cw, cd, name)
            reads.append((prefix, strinc(prefix)))
            c = int(self._middle[name])
        else:
            c = int(self.c[t])
        rows = [_W(b"W", w), _WD(b"D", w, d), _WDC(b"C", cw, cd, c)]
        reads += [point(x) for x in rows]
        # the history row: a key no other transaction has (the sequence
        # number is this transaction's place in the stream)
        writes = [point(x) for x in rows + [_HIST(b"H", cw, cd, c, t)]]
        return reads, writes

    def _delivery(self, t: int) -> tuple:
        w = int(self.w[t])
        reads, writes = [], []
        for d in range(1, DISTRICTS + 1):
            prefix = _WD(b"N", w, d)
            o = self.undelivered.get((w, d), FIRST_UNDELIVERED)
            if o >= self.next_order.get((w, d), FIRST_NEW_ORDER):
                # nothing to deliver: the getRange read the whole prefix
                reads.append((prefix, strinc(prefix)))
                continue
            self.undelivered[(w, d)] = o + 1
            n, c = self.orders.pop((w, d, o), None) or loaded_order(w, d, o)
            found = _WDO(b"N", w, d, o)
            lines = _WDO(b"P", w, d, o)
            order, customer = _WDO(b"O", w, d, o), _WDC(b"C", w, d, c)
            # getRange(prefix, limit 1) conflicts on [prefix, found + \0)
            reads += [(prefix, found + b"\x00"), point(order),
                      (lines, strinc(lines)), point(customer)]
            writes += [point(found), point(order)]
            writes += [point(_WDOL(b"P", w, d, o, ol))
                       for ol in range(1, n + 1)]
            writes.append(point(customer))
        return reads, writes

    def batch(self, n: int, size: int) -> list:
        if n * size != self._at:
            raise ValueError(f"batch {n} dealt out of turn (at transaction "
                             f"{self._at}); replay() starts over")
        if self._at + size > self.n_txns:
            raise ValueError(f"the deal holds {self.n_txns} transactions")
        profile = (self._new_order, self._payment, self._delivery)
        out = []
        for t, kind in enumerate(self.kinds[self._at:self._at + size].tolist(),
                                 self._at):
            reads, writes = profile[kind](t)
            self.ranges += len(reads) + len(writes)
            for b, e in reads:
                if e != b + b"\x00":
                    self.true_ranges += 1
            self.longest_key = max(self.longest_key, max(
                len(e) for _b, e in reads + writes))
            out.append((kind, reads, writes))
        self._at += size
        return out

// C ABI for the native host core. Exports:
//
// - nat_prep_lanes: batch lane preparation for the TPU verify kernel —
//   the native twin of TpuSecpVerifier._prep_lanes + _pack_lanes
//   (crypto/jax_backend.py): structural pubkey parse, lax-DER, high-S
//   normalization, Montgomery-batched s^-1 mod n, BIP340 challenge
//   hashing, GLV lambda split, byte packing. One call per dispatch chunk.
// - nat_verify_{ecdsa,schnorr}, nat_tweak_add_check: full host-exact
//   single verifies (the scalar fallback path).
// - nat_sha256 / nat_sha256d / nat_tagged_hash: hashing utilities.
//
// Layouts must stay bit-identical to the Python packers; the test suite
// asserts equality lane by lane (tests/test_native.py).

#include "block.hpp"
#include "eval.hpp"
#include "lru.hpp"
#include "secp.hpp"

#include <algorithm>
#include <cstdint>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <memory>
#include <pthread.h>

using namespace nat;

namespace {

constexpr int KIND_ECDSA = 0;
constexpr int KIND_SCHNORR = 1;
constexpr int KIND_TWEAK = 2;

struct Lane {
    // mirrors jax_backend._Lane defaults
    bool valid = false;
    Sc a{};                    // fixed-base scalar
    u64 b1[2] = {0, 0};        // |GLV half 1| little-endian
    u64 b2[2] = {0, 0};
    i32 neg1 = 0, neg2 = 0;
    U256 px{};                 // raw x (defaults to G_X below)
    i32 want_odd = 0;
    U256 t1{};                 // raw target
    i32 has_t2 = 0;
    i32 parity = -1;
};

inline void set_b(Lane& ln, const Sc& b) {
    GlvSplit sp = split_lambda(b);
    if (!sp.ok) {  // cannot happen for k < n; defensive
        ln.valid = false;
        return;
    }
    ln.b1[0] = sp.a1[0];
    ln.b1[1] = sp.a1[1];
    ln.b2[0] = sp.a2[0];
    ln.b2[1] = sp.a2[1];
    ln.neg1 = sp.neg1;
    ln.neg2 = sp.neg2;
}

// Structural half of pubkey parsing (jax_backend._host_parse_pubkey): no
// square root for compressed keys — the y lift happens on device from
// (x, want_odd); the 65-byte form shares parse_uncompressed_pubkey with
// the host-exact verify path.
inline bool host_parse_pubkey(Lane& ln, const u8* pk, i64 len) {
    if (len == 33 && (pk[0] == 2 || pk[0] == 3)) {
        U256 x = u256_from_be(pk + 1);
        if (u256_cmp(x, FIELD_P()) >= 0) return false;
        ln.px = x;
        ln.want_odd = pk[0] == 3 ? 1 : 0;
        return true;
    }
    if (len == 65 && (pk[0] == 4 || pk[0] == 6 || pk[0] == 7)) {
        Fe x, y;
        if (!parse_uncompressed_pubkey(pk, &x, &y)) return false;
        ln.px = x.n;
        ln.want_odd = fe_is_odd(y) ? 1 : 0;
        return true;
    }
    return false;
}

// Shared bodies for the records/spec drain trios and the single/batched
// verify surfaces (one implementation, two wire paths).

void fill_records_meta(const std::vector<Record>& v, i32* kinds, i32* parities,
                       i64* lens) {
    for (size_t i = 0; i < v.size(); i++) {
        const Record& r = v[i];
        kinds[i] = r.kind;
        parities[i] = r.parity;
        lens[3 * i] = (i64)r.p0.size();
        lens[3 * i + 1] = (i64)r.p1.size();
        lens[3 * i + 2] = (i64)r.p2.size();
    }
}

i64 records_total_bytes(const std::vector<Record>& v) {
    i64 total = 0;
    for (const Record& r : v)
        total += (i64)(r.p0.size() + r.p1.size() + r.p2.size());
    return total;
}

void fill_records_data(const std::vector<Record>& v, u8* blob) {
    size_t pos = 0;
    for (const Record& r : v) {
        std::memcpy(blob + pos, r.p0.data(), r.p0.size());
        pos += r.p0.size();
        std::memcpy(blob + pos, r.p1.data(), r.p1.size());
        pos += r.p1.size();
        std::memcpy(blob + pos, r.p2.data(), r.p2.size());
        pos += r.p2.size();
    }
}

// One input through verify_script with a (possibly deferring) checker;
// bounds-checks n_in. Does NOT touch the session's records/unknown state —
// callers own the clear/boundary bookkeeping. *walk, where asked for, gets
// the pairings the script's CHECKMULTISIG walks tried.
i32 run_verify_input(Session* sess, NTx* tx, i32 n_in, i64 amount,
                     const u8* spk, i64 spk_len, i32 flags, i32 mode,
                     i32* script_err, i32* unknown, i64* walk = nullptr) {
    if (walk) *walk = 0;
    if (n_in < 0 || (size_t)n_in >= tx->vin.size()) {
        *script_err = SE_UNKNOWN_ERROR;
        *unknown = 0;
        return 0;
    }
    if (sess) sess->unknown = 0;
    Checker checker;
    checker.tx = tx;
    checker.n_in = (size_t)n_in;
    checker.amount = amount;
    checker.mode = mode;
    checker.sess = sess;
    Bytes spk_b(spk, spk + spk_len);
    EvalResult r = verify_script(tx->vin[(size_t)n_in].script_sig, spk_b,
                                 tx->vin[(size_t)n_in].witness, (u32)flags,
                                 checker);
    *script_err = r.err;
    *unknown = sess ? sess->unknown : 0;
    if (walk) *walk = checker.walk_pairings;
    return r.ok ? 1 : 0;
}

// The one spawn-and-join of this file (no pool): fn(t, lo, hi) on worker t
// of T over the contiguous shards [n*t/T, n*(t+1)/T) of [0, n). That split
// is the whole schedule where a row costs what its neighbour does and writes
// rows of its own (uniq_lanes, uniq_digests); the interpreter's inputs do
// not cost alike, so nat_verify_inputs_idx asks for one unit a worker
// (n == T) and lets each draw its inputs from a shared cursor instead.
// Workers get WORKER_STACK bytes, not the 8 MB default: glibc keeps only
// 40 MB of exited threads' stacks, so from the sixth worker on every
// spawn mapped a fresh stack and every exit unmapped one (0.3 ms a thread
// on the chip's host, more than a 600-lane shard's work). Nothing a
// worker runs recurses or holds more than a few KB of locals. A worker
// that cannot be made runs on the caller, and so does everything at T < 2.
//
// Given `stats` (a session's: interp.hpp FanStats) it is also the one place
// that times threads: it stamps its entry and the last join, every worker
// its first and last instruction into its own Job, and the six sums are
// added after the join. Without, no clock is read.
constexpr size_t WORKER_STACK = 1 << 20;

template <class Fn>
void fan_out(i32 n, i32 T, Fn fn, FanStats* stats = nullptr) {
    const i64 entered = stats ? steady_ns() : 0;
    if (T < 2) {
        fn(0, 0, n);
        if (stats) {
            i64 wall = steady_ns() - entered;
            for (int k : {FanStats::WALL, FanStats::HELD, FanStats::SUM, FanStats::MAX})
                stats->ns[k] += wall;
        }
        return;
    }
    struct Job {
        Fn* fn;
        i32 t, lo, hi;
        pthread_t id;
        bool spawned;
        bool timed;
        i64 first, last;
        void run() {
            if (timed) first = steady_ns();
            (*fn)(t, lo, hi);
            if (timed) last = steady_ns();
        }
    };
    std::vector<Job> jobs((size_t)T);
    pthread_attr_t attr;
    pthread_attr_init(&attr);
    pthread_attr_setstacksize(&attr, WORKER_STACK);
    for (i32 t = 0; t < T; t++) {
        Job& j = jobs[(size_t)t];
        j = Job{&fn, t, (i32)((i64)n * t / T), (i32)((i64)n * (t + 1) / T),
                pthread_t(), false, stats != nullptr, 0, 0};
        auto body = [](void* p) -> void* {
            static_cast<Job*>(p)->run();
            return nullptr;
        };
        j.spawned = pthread_create(&j.id, &attr, body, &j) == 0;
        if (!j.spawned) j.run();
    }
    pthread_attr_destroy(&attr);
    for (Job& j : jobs)
        if (j.spawned) pthread_join(j.id, nullptr);
    if (!stats) return;
    const i64 joined = steady_ns();
    i64 sum = 0, max = 0, first = entered, last = entered;
    for (const Job& j : jobs) {
        sum += j.last - j.first;
        max = std::max(max, j.last - j.first);
        first = std::max(first, j.first);
        last = std::max(last, j.last);
    }
    stats->ns[FanStats::WALL] += joined - entered;
    stats->ns[FanStats::HELD] += (joined - entered) * T;
    stats->ns[FanStats::SUM] += sum;
    stats->ns[FanStats::MAX] += max;
    stats->ns[FanStats::START_LAG] += first - entered;
    stats->ns[FanStats::TAIL] += joined - last;
}

// Width of the lane-prep fan-out (uniq_lanes, uniq_digests): one shard per
// PREP_SHARD_MIN entries up to `n_threads`, and no thread at all under
// PREP_SERIAL_BELOW, where spawn and join cost what the work does (a warm
// connect's ~400 lanes, a served batch's ~13). Read on the chip's host
// (PERF.md, PR 30): a worker costs ~0.1 ms to make and join, 512 lanes
// take 0.7 ms to prep and 0.2 ms to digest, and 1,024 entries on four
// workers took what they take on one.
constexpr i32 PREP_SERIAL_BELOW = 1024;
constexpr i32 PREP_SHARD_MIN = 512;

inline i32 prep_shards(i32 n, i32 n_threads) {
    if (n < PREP_SERIAL_BELOW) return 1;
    return std::max(1, std::min(n_threads, n / PREP_SHARD_MIN));
}

// --- Reference-compatible libbitcoinconsensus ABI -------------------------
// Drop-in twins of the reference's three exported symbols
// (bitcoinconsensus.h:67-75): same signatures, same error enum
// (bitcoinconsensus.h:38-46), same check ordering (flags -> deserialize ->
// index -> size, bitcoinconsensus.cpp:79-102). Consumers that link
// libbitcoinconsensus can link libnat instead; tests/test_drop_in_abi.py
// replays the differential corpus through BOTH .so's via one ctypes path.

constexpr i32 BC_ERR_OK = 0;
constexpr i32 BC_ERR_TX_INDEX = 1;
constexpr i32 BC_ERR_TX_SIZE_MISMATCH = 2;
constexpr i32 BC_ERR_TX_DESERIALIZE = 3;
constexpr i32 BC_ERR_AMOUNT_REQUIRED = 4;
constexpr i32 BC_ERR_INVALID_FLAGS = 5;

// bitcoinconsensus_SCRIPT_FLAGS_VERIFY_ALL (bitcoinconsensus.h:49-61):
// P2SH | DERSIG | NULLDUMMY | CHECKLOCKTIMEVERIFY | CHECKSEQUENCEVERIFY |
// WITNESS. Anything outside is rejected (verify_flags,
// bitcoinconsensus.cpp:74-77).
constexpr u32 BC_FLAGS_VERIFY_ALL =
    (1u << 0) | (1u << 2) | (1u << 4) | (1u << 9) | (1u << 10) | (1u << 11);

inline int bc_set_error(i32* err, i32 code) {
    if (err) *err = code;
    return 0;
}

int bc_verify(const u8* spk, u32 spk_len, i64 amount, const u8* tx_to,
              u32 tx_to_len, u32 n_in, u32 flags, i32* err) {
    if (flags & ~BC_FLAGS_VERIFY_ALL)
        return bc_set_error(err, BC_ERR_INVALID_FLAGS);
    try {
        std::unique_ptr<NTx> tx(tx_parse(tx_to, (size_t)tx_to_len));
        if (n_in >= tx->vin.size()) return bc_set_error(err, BC_ERR_TX_INDEX);
        // Exact re-serialization check (bitcoinconsensus.cpp:91-92):
        // trailing bytes or non-canonical encodings that still parse must
        // report TX_SIZE_MISMATCH.
        if (tx->ser_size != (i64)tx_to_len)
            return bc_set_error(err, BC_ERR_TX_SIZE_MISMATCH);
        // Regardless of the verification result, the tx did not error
        // (bitcoinconsensus.cpp:94-95).
        bc_set_error(err, BC_ERR_OK);
        precompute(*tx, nullptr);
        i32 script_err, unknown;
        return run_verify_input(nullptr, tx.get(), (i32)n_in, amount, spk,
                                (i64)spk_len, (i32)flags, MODE_EXACT,
                                &script_err, &unknown);
    } catch (...) {
        // Same fence as the reference shim (bitcoinconsensus.cpp:99-101).
        return bc_set_error(err, BC_ERR_TX_DESERIALIZE);
    }
}

}  // namespace

extern "C" {

// 4: nat_session_recidx_data grew a capacity argument + i64 return;
//    the nat_block_* / nat_view_* block layer landed.
// 5: nat_block_tx_ptrs.
// 6: apply_block's undo record, nat_view_undo_block.
// 7: the index-mode session keeps its checks in one arena and its verdicts
//    by uniq index (same symbols; an older .so is a different structure).
// 8: nat_session_uniq_lanes / nat_session_uniq_digests take n_threads;
//    nat_prep_shards.
// 9: nat_session_spec_pairings.
// 10: nat_block_accounting takes the script cache's salt and makes the
//     keys; nat_block_script_keys copies them out; nat_block_nowit_sizes.
// 11: nat_session_sighashes.
// 12: nat_session_lane_kinds, nat_session_taproot_hashes.
// 13: nat_block_coin_probes; the coin tables key on a fixed 36-byte outpoint
//     under a salted hash (same symbols, another NView).
// 14: nat_session_call_walks, nat_store_pool_bytes.
// 15: nat_session_sighash_work, nat_sha256_uses_sha_ni.
// 16: nat_session_sighash_work also writes the legacy template's two counts.
// 17: and its third (`resumed`); nat_session_worker_ns.
// 18: nat_view_disconnect_block, nat_undo_matches_block; nat_view_digest
//     takes n_threads.
// 19: nat_lru_* (native/lru.hpp): the success caches' key set.
// 20: nat_session_stages, nat_block_stages (the native stage clock); 17's
//     accessor went into the former.
int nat_version() { return 20; }

// --- Success caches' key set (native/lru.hpp) ------------------------------
//
// Every call takes the set's mutex once. A return of -2 is an allocation
// that failed before the set was touched.

void* nat_lru_new(i64 max_entries) {
    try {
        return new LruSet(max_entries);
    } catch (...) {
        return nullptr;
    }
}

void nat_lru_free(void* s) { delete static_cast<LruSet*>(s); }

i64 nat_lru_len(void* s) { return static_cast<LruSet*>(s)->size(); }

// present[j] = keys[32 j ..] is in the set, for j < n; `erase` erases a hit,
// `fabricated` counts an absent key as a hit (LruSet::probe). Returns the
// size afterwards.
i64 nat_lru_probe(void* s, const u8* keys, i64 n, i32 erase, i32 fabricated,
                  u8* present, i64* n_present) {
    return static_cast<LruSet*>(s)->probe(keys, n, erase != 0, fabricated != 0,
                                          present, n_present);
}

// Insert keys[32 idx[j] ..] for j < n, or keys[32 j ..] where idx is null;
// `n_keys` digests lie in `keys`. out: inserted, evicted. Returns the size
// afterwards, -1 for an index outside the blob (nothing inserted).
i64 nat_lru_add(void* s, const u8* keys, i64 n_keys, const i64* idx, i64 n,
                i64* out) {
    if (idx) {
        for (i64 j = 0; j < n; j++)
            if (idx[j] < 0 || idx[j] >= n_keys) return -1;
    } else if (n > n_keys) {
        return -1;
    }
    try {
        return static_cast<LruSet*>(s)->add(keys, idx, n, out, out + 1);
    } catch (...) {
        return -2;
    }
}

i64 nat_lru_discard(void* s, const u8* key, i32* was_present) {
    return static_cast<LruSet*>(s)->discard(key, was_present);
}

// The keys oldest first into out[32 room]; returns the set's size.
i64 nat_lru_keys(void* s, u8* out, i64 room) {
    return static_cast<LruSet*>(s)->keys(out, room);
}

// out[5]: hits, misses, insertions, evictions, erases.
void nat_lru_counters(void* s, i64* out) {
    static_cast<LruSet*>(s)->counters(out);
}

// --- Block layer (native/block.hpp) ---------------------------------------

void* nat_block_parse(const u8* data, i64 len) {
    try {
        return block_parse(data, (size_t)len);
    } catch (...) {
        return nullptr;
    }
}

void nat_block_free(void* b) { delete static_cast<NBlock*>(b); }

i32 nat_block_n_tx(void* b) {
    return (i32)static_cast<NBlock*>(b)->vtx.size();
}

// Total non-coinbase inputs (the script-phase lane count).
i32 nat_block_n_inputs(void* b) {
    auto* blk = static_cast<NBlock*>(b);
    i64 n = 0;
    for (const auto& tx : blk->vtx)
        if (!tx_is_coinbase(*tx)) n += (i64)tx->vin.size();
    return (i32)n;
}

// Borrowed pointer into the block (freed with the block, never by
// nat_tx_free).
void* nat_block_tx(void* b, i32 i) {
    auto* blk = static_cast<NBlock*>(b);
    if (i < 0 || (size_t)i >= blk->vtx.size()) return nullptr;
    return blk->vtx[(size_t)i].get();
}

// Every tx of the block at once: the first min(cap, n_tx) borrowed
// pointers go to out, n_tx is returned (the index-mode driver gathers its
// per-input pointer column from this table with one array index).
i32 nat_block_tx_ptrs(void* b, void** out, i32 cap) {
    auto* blk = static_cast<NBlock*>(b);
    i32 n = (i32)blk->vtx.size();
    for (i32 i = 0; i < n && i < cap; i++) out[i] = blk->vtx[(size_t)i].get();
    return n;
}

void nat_block_txid(void* b, i32 i, u8* out32) {
    auto* blk = static_cast<NBlock*>(b);
    std::memcpy(out32, blk->txids[(size_t)i].data(), 32);
}

void nat_block_wtxid(void* b, i32 i, u8* out32) {
    auto* blk = static_cast<NBlock*>(b);
    std::memcpy(out32, blk->wtxids[(size_t)i].data(), 32);
}

// Per-tx serialized sizes without witness (the weight rule's base size).
// out: n_tx entries.
void nat_block_nowit_sizes(void* b, i64* out) {
    const std::vector<i64>& sizes = static_cast<NBlock*>(b)->nowit_size;
    if (!sizes.empty())
        std::memcpy(out, sizes.data(), sizes.size() * sizeof(i64));
}

// Context-free CheckBlock; returns a BlkReason code (0 = ok).
i32 nat_block_check(void* b, i32 do_pow, const u8* pow_limit_be,
                    i32 do_merkle) {
    return check_block(*static_cast<NBlock*>(b), do_pow != 0, pow_limit_be,
                       do_merkle != 0);
}

i32 nat_block_check_witness(void* b) {
    return check_witness_commitment(*static_cast<NBlock*>(b));
}

// ConnectBlock accounting. With a salt (the script-execution cache's) it
// also makes every input's cache key, for nat_block_script_keys to hand
// out; `salt` NULL makes none.
i32 nat_block_accounting(void* b, void* v, i64 height, i32 flags,
                         const u8* salt, i64 salt_len) {
    return block_accounting(*static_cast<NBlock*>(b), *static_cast<NView*>(v),
                            height, (u32)flags, salt, (size_t)salt_len);
}

void nat_block_acct_meta(void* b, i64* fees, i64* sigop_cost, i64* n_inputs,
                         i64* spk_bytes) {
    const BlockAcct& A = static_cast<NBlock*>(b)->acct;
    *fees = A.fees;
    *sigop_cost = A.sigop_cost;
    *n_inputs = (i64)A.tx_index.size();
    *spk_bytes = (i64)A.spk_blob.size();
}

void nat_block_acct_data(void* b, i32* tx_index, i32* n_in, i64* amounts,
                         i64* spk_offs, u8* spk_blob) {
    auto* blk = static_cast<NBlock*>(b);
    i64 at = steady_ns();
    const BlockAcct& A = blk->acct;
    size_t n = A.tx_index.size();
    if (n) {
        std::memcpy(tx_index, A.tx_index.data(), n * sizeof(i32));
        std::memcpy(n_in, A.n_in.data(), n * sizeof(i32));
        std::memcpy(amounts, A.amounts.data(), n * sizeof(i64));
    }
    std::memcpy(spk_offs, A.spk_offs.data(), (n + 1) * sizeof(i64));
    if (!A.spk_blob.empty())
        std::memcpy(spk_blob, A.spk_blob.data(), A.spk_blob.size());
    blk->stages.stamp(NBlock::ST_COPY, at);
}

// Per-tx spent-output digests (models/sigcache.py spent_digest stream);
// coinbase rows are zero. out: n_tx * 32 bytes.
void nat_block_spent_digests(void* b, u8* out) {
    const BlockAcct& A = static_cast<NBlock*>(b)->acct;
    for (size_t t = 0; t < A.spent_digests.size(); t++)
        std::memcpy(out + 32 * t, A.spent_digests[t].data(), 32);
}

// Script-execution-cache keys for every non-coinbase input, as the
// accounting call made them from its salt and flags: the models/sigcache.py
// `_key(_parts(wtxid, n_in, flags, spent_digest))` stream. out: n_inputs*32.
// Returns the bytes written (0: accounting ran without a salt, or not at
// all).
i64 nat_block_script_keys(void* b, u8* out) {
    const Bytes& keys = static_cast<NBlock*>(b)->acct.script_keys;
    if (!keys.empty()) std::memcpy(out, keys.data(), keys.size());
    return (i64)keys.size();
}

// Hash-table probes the block's last accounting and the applies since made:
// out[0] of the view, out[1] of the block's own coin table.
void nat_block_coin_probes(void* b, i64* out) {
    auto* blk = static_cast<NBlock*>(b);
    out[0] = blk->view_probes;
    out[1] = blk->block_probes;
}

// The block's native stage clock (block.hpp NBlock::stages): what its last
// accounting spent in pass 1 (decide), in pass 2 (fill) and in
// nat_block_acct_data's copy since. out[0..3) nanoseconds, out[3..6) the
// times each was stamped. An accounting starts all at zero.
void nat_block_stages(void* b, i64* out) {
    static_cast<NBlock*>(b)->stages.read(out);
}

void* nat_view_new() { return new NView(); }

void nat_view_free(void* v) { delete static_cast<NView*>(v); }

void* nat_view_clone(void* v) {
    return new NView(*static_cast<NView*>(v));
}

i64 nat_view_len(void* v) {
    return (i64)static_cast<NView*>(v)->map.size();
}

// Batch coin insert: coin i is (txids[32i..32i+32), ns[i]) ->
// (values[i], heights[i], coinbases[i], spk_blob[spk_offs[i]..spk_offs[i+1])).
void nat_view_add_coins(void* v, i32 n, const u8* txids, const i32* ns,
                        const i64* values, const i32* heights,
                        const i32* coinbases, const u8* spk_blob,
                        const i64* spk_offs) {
    auto* view = static_cast<NView*>(v);
    for (i32 i = 0; i < n; i++) {
        NCoin c;
        c.value = values[i];
        c.height = heights[i];
        c.coinbase = coinbases[i] != 0;
        c.spk.assign(spk_blob + spk_offs[i], spk_blob + spk_offs[i + 1]);
        view->map.insert_or_assign(
            NView::key(txids + 32 * (size_t)i, (u32)ns[i]), std::move(c));
    }
}

// Point query: returns 1 if present (filling value/height/coinbase/spk_len),
// else 0. The scriptPubKey bytes follow via nat_view_get_spk.
i32 nat_view_get(void* v, const u8* txid, i32 n, i64* value, i32* height,
                 i32* coinbase, i64* spk_len) {
    auto* view = static_cast<NView*>(v);
    auto it = view->map.find(NView::key(txid, (u32)n));
    if (it == view->map.end()) return 0;
    *value = it->second.value;
    *height = it->second.height;
    *coinbase = it->second.coinbase ? 1 : 0;
    *spk_len = (i64)it->second.spk.size();
    return 1;
}

void nat_view_get_spk(void* v, const u8* txid, i32 n, u8* out) {
    auto* view = static_cast<NView*>(v);
    auto it = view->map.find(NView::key(txid, (u32)n));
    if (it == view->map.end()) return;
    std::memcpy(out, it->second.spk.data(), it->second.spk.size());
}

i32 nat_view_spend(void* v, const u8* txid, i32 n) {
    auto* view = static_cast<NView*>(v);
    return view->map.erase(NView::key(txid, (u32)n)) ? 1 : 0;
}

void nat_view_apply_block(void* v, void* b, i64 height) {
    view_apply_block(*static_cast<NView*>(v), *static_cast<NBlock*>(b),
                     height);
}

// Apply that keeps what it removed: returns a new undo record (free with
// nat_undo_free) holding the coins the block spent.
void* nat_view_apply_block_undo(void* v, void* b, i64 height) {
    auto* undo = new NBlockUndo();
    view_apply_block(*static_cast<NView*>(v), *static_cast<NBlock*>(b),
                     height, undo);
    return undo;
}

// The inverse of nat_view_apply_block_undo for the same block, on a view
// in the state that apply left: 1 when the view was put back (the record
// keeps its coins), 0 when the record was not made from such a block (the
// view is untouched).
i32 nat_view_undo_block(void* v, void* b, void* u) {
    return view_undo_block(*static_cast<NView*>(v), *static_cast<NBlock*>(b),
                           *static_cast<NBlockUndo*>(u))
               ? 1
               : 0;
}

// DisconnectBlock for a block connected with a record (block.hpp
// undo_matches_block, then view_disconnect_block): 0 ok, 1 unclean, 2
// failed; the view is written on 0 alone. `checked`: the caller has held
// the record against this block already (nat_undo_matches_block gave 1)
// and the check is not made again. out[0]: the view's probes, out[1]:
// coins restored, out[2]: outputs removed.
i32 nat_view_disconnect_block(void* v, void* b, void* u, i64 height,
                              i32 checked, i64* out) {
    const NBlock& blk = *static_cast<NBlock*>(b);
    const NBlockUndo& undo = *static_cast<NBlockUndo*>(u);
    DisconnectStats st;
    i32 r = checked || undo_matches_block(blk, undo)
                ? view_disconnect_block(*static_cast<NView*>(v), blk, undo,
                                        height, st)
                : (i32)DISCONNECT_FAILED;
    out[0] = st.probes;
    out[1] = st.restored;
    out[2] = st.removed;
    return r;
}

// Whether the record was made from a block of these transactions
// (block.hpp undo_matches_block): 1 or 0.
i32 nat_undo_matches_block(void* u, void* b) {
    return undo_matches_block(*static_cast<NBlock*>(b),
                              *static_cast<NBlockUndo*>(u))
               ? 1
               : 0;
}

// Coins the record holds (spent + overwritten).
i64 nat_undo_len(void* u) {
    auto* undo = static_cast<NBlockUndo*>(u);
    return (i64)(undo->spent.size() + undo->replaced.size());
}

void nat_undo_free(void* u) { delete static_cast<NBlockUndo*>(u); }

// out: 32 bytes (block.hpp view_digest_buckets), the map's buckets cut
// over one worker a DIGEST_SHARD_MIN coins up to `n_threads`: a million
// coins take a third of a second on one, chasing a node a coin.
void nat_view_digest(void* v, u8* out, i32 n_threads) {
    const NView& view = *static_cast<NView*>(v);
    constexpr size_t DIGEST_SHARD_MIN = 1 << 16;
    i32 T = (i32)std::max<size_t>(
        1, std::min<size_t>((size_t)std::max(n_threads, 1),
                            view.map.size() / DIGEST_SHARD_MIN));
    size_t buckets = view.map.bucket_count();
    std::vector<Hash32> parts((size_t)T);
    fan_out(T, T, [&](i32 t, i32, i32) {
        view_digest_buckets(view, buckets * (size_t)t / (size_t)T,
                            buckets * (size_t)(t + 1) / (size_t)T,
                            parts[(size_t)t].data());
    });
    std::memset(out, 0, 32);
    for (const Hash32& p : parts)
        for (int j = 0; j < 32; j++) out[j] ^= p[(size_t)j];
}

// The three libbitcoinconsensus exports (bitcoinconsensus.h:67-75).

int bitcoinconsensus_verify_script_with_amount(
    const unsigned char* scriptPubKey, unsigned int scriptPubKeyLen,
    int64_t amount, const unsigned char* txTo, unsigned int txToLen,
    unsigned int nIn, unsigned int flags, i32* err) {
    return bc_verify(scriptPubKey, scriptPubKeyLen, (i64)amount, txTo, txToLen,
                     nIn, flags, err);
}

int bitcoinconsensus_verify_script(const unsigned char* scriptPubKey,
                                   unsigned int scriptPubKeyLen,
                                   const unsigned char* txTo,
                                   unsigned int txToLen, unsigned int nIn,
                                   unsigned int flags, i32* err) {
    // The amount-less entry cannot serve BIP143 sighashes: WITNESS
    // requires an amount (bitcoinconsensus.cpp:115-121).
    if (flags & (1u << 11)) return bc_set_error(err, BC_ERR_AMOUNT_REQUIRED);
    return bc_verify(scriptPubKey, scriptPubKeyLen, 0, txTo, txToLen, nIn,
                     flags, err);
}

unsigned int bitcoinconsensus_version() {
    return 1;  // BITCOINCONSENSUS_API_VER (bitcoinconsensus.h:36)
}

unsigned int nat_murmur3_32(unsigned int seed, const u8* data, i64 len) {
    return murmur3_32(seed, data, (size_t)len);
}

void nat_sha256(const u8* data, i64 len, u8* out32) {
    sha256(data, (size_t)len, out32);
}

void nat_sha256d(const u8* data, i64 len, u8* out32) {
    sha256d(data, (size_t)len, out32);
}

void nat_tagged_hash(const u8* tag, i64 taglen, const u8* data, i64 len,
                     u8* out32) {
    u8 th[32];
    sha256(tag, (size_t)taglen, th);
    Sha256 h;
    h.write(th, 32);
    h.write(th, 32);
    h.write(data, (size_t)len);
    h.finalize(out32);
}

int nat_verify_ecdsa(const u8* pub, i64 publen, const u8* sig, i64 siglen,
                     const u8* msg32) {
    return verify_ecdsa(pub, (size_t)publen, sig, (size_t)siglen, msg32) ? 1 : 0;
}

int nat_verify_schnorr(const u8* pk32, const u8* sig64, const u8* msg32) {
    return verify_schnorr(pk32, sig64, msg32) ? 1 : 0;
}

int nat_tweak_add_check(const u8* tweaked32, i32 parity, const u8* internal32,
                        const u8* tweak32) {
    return tweak_add_check(tweaked32, parity, internal32, tweak32) ? 1 : 0;
}

inline PartsView parts_from_wire(const u8* blob, const i64* offs,
                                 const i32* kinds, i32 i) {
    return PartsView{
        kinds[i] & 0xff,          (kinds[i] >> 8) & 1,
        blob + offs[3 * i],       offs[3 * i + 1] - offs[3 * i],
        blob + offs[3 * i + 1],   offs[3 * i + 2] - offs[3 * i + 1],
        blob + offs[3 * i + 2],   offs[3 * i + 3] - offs[3 * i + 2],
    };
}

// Lane-prep part order of a check held in record order (PartsView's): the
// prep core expects tweak checks as internal32 | tweak32 | tweaked32 (the
// prep_pack wire permutation).
inline PartsView lanes_order(const PartsView& v) {
    if (v.kind == KIND_TWEAK)
        return PartsView{v.kind, v.parity, v.p1, v.l1, v.p2, v.l2, v.p0, v.l0};
    return v;
}

// Lane-prep core: parts -> packed kernel lanes. Parts per kind:
//     ecdsa:   pubkey | sig_der | msg32
//     schnorr: pk32   | sig64   | msg32
//     tweak:   internal32 | tweak32 | tweaked32
// Outputs (caller-allocated, only the first n lanes are written):
//   fields: n*128 bytes — per lane (a | b1 | b2 | px | t1) little-endian
//   want_odd/parity/has_t2/neg1/neg2/valid: n x i32 each
// One shard: lanes parts[0..n) into the first n slots of the outputs.
void prep_lanes_range(const PartsView* parts, i32 n, u8* fields,
                      i32* want_odd, i32* parity, i32* has_t2, i32* neg1,
                      i32* neg2, i32* valid) {
    // Pass 1: parse everything; collect ECDSA (r, s, m) for the batched
    // inversion (jax_backend._batch_inv_mod_n shape: one Fermat total).
    std::vector<Lane> lanes((size_t)n);
    std::vector<i32> ecdsa_idx((size_t)n);
    std::vector<Sc> ecdsa_r((size_t)n);
    std::vector<Sc> ecdsa_s((size_t)n);
    std::vector<Sc> ecdsa_m((size_t)n);
    i32 n_ecdsa = 0;

    for (i32 i = 0; i < n; i++) {
        Lane& ln = lanes[i];
        ln.px = GEN().x.n;  // invalid-lane default matches _Lane (G_X)
        const u8* p0 = parts[i].p0;
        i64 l0 = parts[i].l0;
        const u8* p1 = parts[i].p1;
        i64 l1 = parts[i].l1;
        const u8* p2 = parts[i].p2;
        i64 l2 = parts[i].l2;
        int kind = parts[i].kind;
        if (kind == KIND_ECDSA) {
            if (l2 != 32) continue;
            if (!host_parse_pubkey(ln, p0, l0)) continue;
            Sc r, s;
            if (!parse_der_lax(p1, (size_t)l1, &r, &s)) continue;
            if (sc_is_high(s)) s = sc_neg(s);
            if (sc_is_zero(r) || sc_is_zero(s)) continue;
            ln.t1 = r.n;
            U256 rn;
            u64 carry = u256_add(rn, r.n, ORDER_N());
            ln.has_t2 = (!carry && u256_cmp(rn, FIELD_P()) < 0) ? 1 : 0;
            ln.valid = true;
            ecdsa_idx[n_ecdsa] = i;
            ecdsa_r[n_ecdsa] = r;
            ecdsa_s[n_ecdsa] = s;
            ecdsa_m[n_ecdsa] = sc_from_be(p2);
            n_ecdsa++;
        } else if (kind == KIND_SCHNORR) {
            if (l0 != 32 || l1 != 64 || l2 != 32) continue;
            U256 px = u256_from_be(p0);
            if (u256_cmp(px, FIELD_P()) >= 0) continue;
            U256 r_u = u256_from_be(p1);
            U256 s_u = u256_from_be(p1 + 32);
            if (u256_cmp(r_u, FIELD_P()) >= 0) continue;
            if (u256_cmp(s_u, ORDER_N()) >= 0) continue;
            u8 ch_in[96];
            std::memcpy(ch_in, p1, 32);
            std::memcpy(ch_in + 32, p0, 32);
            std::memcpy(ch_in + 64, p2, 32);
            u8 e_b[32];
            BIP340_CHALLENGE().hash(ch_in, 96, e_b);
            Sc e = sc_from_be(e_b);
            ln.px = px;
            ln.want_odd = 0;
            ln.a.n = s_u;
            set_b(ln, sc_neg(e));  // (n - e) mod n
            ln.t1 = r_u;
            ln.parity = 0;
            ln.valid = true;
        } else if (kind == KIND_TWEAK) {
            if (l0 != 32 || l1 != 32 || l2 != 32) continue;
            U256 px = u256_from_be(p0);
            if (u256_cmp(px, FIELD_P()) >= 0) continue;
            U256 t_u = u256_from_be(p1);
            if (u256_cmp(t_u, ORDER_N()) >= 0) continue;
            ln.px = px;
            ln.want_odd = 0;
            ln.a.n = t_u;
            Sc one;
            one.n = {{1, 0, 0, 0}};
            set_b(ln, one);
            ln.t1 = u256_from_be(p2);  // raw: >= p can never match
            ln.parity = parts[i].parity;
            ln.valid = true;
        }
    }

    // Batched modular inverse of the ECDSA s values (Montgomery trick:
    // one Fermat chain total).
    if (n_ecdsa) {
        std::vector<Sc> prefix((size_t)n_ecdsa);
        Sc acc;
        acc.n = {{1, 0, 0, 0}};
        for (i32 j = 0; j < n_ecdsa; j++) {
            acc = sc_mul(acc, ecdsa_s[j]);
            prefix[j] = acc;
        }
        Sc inv = sc_inv(acc);
        for (i32 j = n_ecdsa - 1; j >= 0; j--) {
            Sc sinv = j ? sc_mul(inv, prefix[j - 1]) : inv;
            inv = sc_mul(inv, ecdsa_s[j]);
            Lane& ln = lanes[ecdsa_idx[j]];
            ln.a = sc_mul(ecdsa_m[j], sinv);      // u1
            set_b(ln, sc_mul(ecdsa_r[j], sinv));  // u2
        }
    }

    // Pack (jax_backend._pack_lanes layout).
    for (i32 i = 0; i < n; i++) {
        const Lane& ln = lanes[i];
        u8* f = fields + (size_t)i * 128;
        u256_to_le(ln.a.n, f);
        for (int j = 0; j < 2; j++) {
            u64 w = ln.b1[j];
            for (int k = 0; k < 8; k++) f[32 + 8 * j + k] = u8(w >> (8 * k));
            w = ln.b2[j];
            for (int k = 0; k < 8; k++) f[48 + 8 * j + k] = u8(w >> (8 * k));
        }
        u256_to_le(ln.px, f + 64);
        u256_to_le(ln.t1, f + 96);
        want_odd[i] = ln.want_odd;
        parity[i] = ln.parity;
        has_t2[i] = ln.has_t2;
        neg1[i] = ln.neg1;
        neg2[i] = ln.neg2;
        valid[i] = ln.valid ? 1 : 0;
    }
}

// All lanes, sharded over prep_shards(n, n_threads) workers: each runs
// the three passes over its own contiguous shard (its own inversion
// chain, exact mod n, so no lane differs from the one-shard run) into
// its slice of the outputs. No shared mutable state, no merge.
void prep_lanes_impl(const std::vector<PartsView>& parts, i32 n_threads,
                     u8* fields, i32* want_odd, i32* parity, i32* has_t2,
                     i32* neg1, i32* neg2, i32* valid,
                     FanStats* stats = nullptr) {
    const i32 n = (i32)parts.size();
    fan_out(n, prep_shards(n, n_threads), [&](i32, i32 lo, i32 hi) {
        prep_lanes_range(parts.data() + lo, hi - lo,
                         fields + (size_t)lo * 128, want_odd + lo,
                         parity + lo, has_t2 + lo, neg1 + lo, neg2 + lo,
                         valid + lo);
    }, stats);
}

// Wire-shape entry (Python packs blob/offs/kinds; kinds[i]&0xff is the
// kind, bit 8 the tweak parity).
void nat_prep_lanes(const u8* blob, const i64* offs, const i32* kinds, i32 n,
                    u8* fields, i32* want_odd, i32* parity, i32* has_t2,
                    i32* neg1, i32* neg2, i32* valid) {
    std::vector<PartsView> parts;
    parts.reserve((size_t)n);
    for (i32 i = 0; i < n; i++)
        parts.push_back(parts_from_wire(blob, offs, kinds, i));
    // Serial: the Python packing loop around this entry dominates it.
    prep_lanes_impl(parts, 1, fields, want_odd, parity, has_t2, neg1, neg2,
                    valid);
}

// ---------------------------------------------------------------------------
// Native interpreter surface: tx handles, deferral sessions, verify_input.
// Twin of core/interpreter.verify_script + models/batch.py
// DeferringSignatureChecker; see native/eval.hpp.

void* nat_session_new() { return new Session(); }

void nat_session_free(void* s) {
    auto* sess = static_cast<Session*>(s);
    if (sess) StorePool::get().give(std::move(sess->uniq));
    delete sess;
}

void nat_session_add_known(void* s, i32 kind, i32 parity, const u8* p0, i64 l0,
                           const u8* p1, i64 l1, const u8* p2, i64 l2,
                           i32 result) {
    static_cast<Session*>(s)->set_known(
        PartsView{kind, parity, p0, l0, p1, l1, p2, l2}, result != 0);
}

i32 nat_session_records_count(void* s) {
    return (i32)static_cast<Session*>(s)->records.size();
}

// kinds/parities: n each; lens: 3n (p0, p1, p2 lengths per record).
void nat_session_records_meta(void* s, i32* kinds, i32* parities, i64* lens) {
    fill_records_meta(static_cast<Session*>(s)->records, kinds, parities, lens);
}

void nat_session_records_data(void* s, u8* blob) {
    fill_records_data(static_cast<Session*>(s)->records, blob);
}

i64 nat_session_records_bytes(void* s) {
    return records_total_bytes(static_cast<Session*>(s)->records);
}

// --- Speculative-record drain (Session::spec; same wire shape as the
// records_* trio). spec_seen persists so re-interpretations never re-emit.

i32 nat_session_spec_count(void* s) {
    return (i32)static_cast<Session*>(s)->spec.size();
}

void nat_session_spec_meta(void* s, i32* kinds, i32* parities, i64* lens) {
    fill_records_meta(static_cast<Session*>(s)->spec, kinds, parities, lens);
}

i64 nat_session_spec_bytes(void* s) {
    return records_total_bytes(static_cast<Session*>(s)->spec);
}

void nat_session_spec_data(void* s, u8* blob) {
    auto* sess = static_cast<Session*>(s);
    fill_records_data(sess->spec, blob);
    sess->spec.clear();  // drained; spec_seen persists across rounds
}

// Batched oracle publish: check i's parts are blob[offs[3i]..offs[3i+1]) etc.
// (Record part order: ecdsa pubkey|sig|msg, schnorr pk32|sig64|msg,
// tweak q32|internal32|tweak32); kinds[i]&0xff is the kind, bit 8 the
// tweak parity; results[i] the verdict.
void nat_session_add_known_batch(void* s, i32 n, const i32* kinds,
                                 const u8* blob, const i64* offs,
                                 const i32* results) {
    auto* sess = static_cast<Session*>(s);
    for (i32 i = 0; i < n; i++)
        sess->set_known(parts_from_wire(blob, offs, kinds, i),
                        results[i] != 0);
}

// Batched salted cache-key digests, byte-identical to the Python
// models/sigcache.py `_key(_parts(kind, data))` stream:
//   sha256(salt || [len(part) as 4-byte LE || part]...)
// with parts = [kind-name, data...] and the tweak parity serialized as an
// 8-byte signed little-endian int between q32 and internal32.
// Digest core shared by the wire and session-resident entries.
void digest_one(const u8* salt, i64 salt_len, const PartsView& pv, u8* out32) {
    static const char* NAMES[3] = {"ecdsa", "schnorr", "tweak"};
    Sha256 h;
    h.write(salt, (size_t)salt_len);
    if (pv.kind > KIND_TWEAK) {
        // An unsynchronized kind table must fail loudly, not read OOB.
        std::fprintf(stderr, "digest_one: bad kind %d\n", pv.kind);
        std::abort();
    }
    auto part = [&h](const u8* p, size_t len) { hash_part(h, p, len); };
    const char* name = NAMES[pv.kind];
    part(reinterpret_cast<const u8*>(name), std::strlen(name));
    part(pv.p0, (size_t)pv.l0);
    if (pv.kind == KIND_TWEAK) {
        u8 pb[8] = {u8(pv.parity & 1), 0, 0, 0, 0, 0, 0, 0};
        part(pb, 8);
    }
    part(pv.p1, (size_t)pv.l1);
    part(pv.p2, (size_t)pv.l2);
    h.finalize(out32);
}

void nat_digest_checks(const u8* salt, i64 salt_len, i32 n, const i32* kinds,
                       const u8* blob, const i64* offs, u8* out) {
    for (i32 i = 0; i < n; i++)
        digest_one(salt, salt_len, parts_from_wire(blob, offs, kinds, i),
                   out + 32 * (size_t)i);
}

// Generic batched salted digests over variable part lists (the script-
// execution-cache keys): item i hashes parts part_bounds[i]..part_bounds[i+1)
// with the models/sigcache.py `_key` stream layout
// (sha256(salt || [len(part) as 4-byte LE || part]...)); part j's bytes are
// blob[part_offs[j]..part_offs[j+1]).
void nat_digest_streams(const u8* salt, i64 salt_len, i32 n,
                        const i64* part_bounds, const i64* part_offs,
                        const u8* blob, u8* out) {
    for (i32 i = 0; i < n; i++) {
        Sha256 h;
        h.write(salt, (size_t)salt_len);
        for (i64 j = part_bounds[i]; j < part_bounds[i + 1]; j++) {
            hash_part(h, blob + part_offs[j],
                      (size_t)(part_offs[j + 1] - part_offs[j]));
        }
        h.finalize(out + 32 * (size_t)i);
    }
}

void* nat_tx_parse(const u8* data, i64 len) {
    try {
        return tx_parse(data, (size_t)len);
    } catch (...) {  // SerErr, bad_alloc, ... — never cross the C ABI
        return nullptr;
    }
}

void nat_tx_wtxid(void* txp, u8* out32) {
    auto* tx = static_cast<NTx*>(txp);
    Bytes b = tx->serialize(true);
    sha256d(b.data(), b.size(), out32);
}

void nat_tx_free(void* tx) { delete static_cast<NTx*>(tx); }

// Serialization export (fuzz harness + consumers needing the canonical
// bytes): two-call pattern — size, then fill.
i64 nat_tx_serialize_size(void* txp, i32 witness) {
    return (i64)static_cast<NTx*>(txp)->serialize(witness != 0).size();
}

void nat_tx_serialize(void* txp, i32 witness, u8* out) {
    Bytes b = static_cast<NTx*>(txp)->serialize(witness != 0);
    std::memcpy(out, b.data(), b.size());
}

i64 nat_tx_ser_size(void* tx) { return static_cast<NTx*>(tx)->ser_size; }

i32 nat_tx_n_inputs(void* tx) {
    return (i32)static_cast<NTx*>(tx)->vin.size();
}

// Precompute the tx-wide hash aggregates; spent outputs (one per input)
// unlock BIP341. spk_offs has n+1 entries into spk_blob.
void nat_tx_set_spent_outputs(void* txp, const i64* amounts, const u8* spk_blob,
                              const i64* spk_offs, i32 n) {
    auto* tx = static_cast<NTx*>(txp);
    std::vector<NTxOut> spent((size_t)n);
    for (i32 i = 0; i < n; i++) {
        spent[i].value = amounts[i];
        spent[i].spk.assign(spk_blob + spk_offs[i], spk_blob + spk_offs[i + 1]);
    }
    precompute(*tx, &spent);
}

void nat_tx_precompute(void* txp) {
    precompute(*static_cast<NTx*>(txp), nullptr);
}

// Verify one input. mode 0 = deferring (records + oracle via sess),
// mode 1 = exact (native curve math; sess may be NULL).
// Returns 1 ok / 0 script-failed; *script_err gets the ScriptError code,
// *unknown the count of oracle misses (deferring mode).
i32 nat_verify_input(void* s, void* txp, i32 n_in, i64 amount, const u8* spk,
                     i64 spk_len, i32 flags, i32 mode, i32* script_err,
                     i32* unknown) {
    auto* sess = static_cast<Session*>(s);
    if (sess) {
        // Symmetric with nat_verify_inputs_idx setting it true: a session
        // that served the index protocol must not keep routing the legacy
        // records path's oracle misses into uniq/rec_idx (the records
        // drain would return 0 entries while unk > 0 and the driver would
        // publish optimistic verdicts with the misses unresolved).
        sess->index_mode = false;
        sess->records.clear();
        sess->call_walk.assign(1, 0);
    }
    return run_verify_input(sess, static_cast<NTx*>(txp), n_in, amount, spk,
                            spk_len, flags, mode, script_err, unknown,
                            sess ? sess->call_walk.data() : nullptr);
}

// Batched verify: n inputs in one call (the per-call ctypes cost of the
// single-input surface dominates a 3k-input block; this removes it).
// txs[i]/n_ins[i]/amounts[i]/flags[i] per input; input i's scriptPubKey is
// spk_blob[spk_offs[i]..spk_offs[i+1]). Outputs per input: ok/err/unk, and
// rec_bounds (n+1 entries) delimiting its slice of the session's records
// (drained afterwards via the records_* trio). Speculative records
// accumulate session-wide; drain via the spec_* trio.
void nat_verify_inputs(void* s, void** txs, const i32* n_ins,
                       const i64* amounts, const u8* spk_blob,
                       const i64* spk_offs, const i32* flags, i32 mode, i32 n,
                       i32* ok, i32* err, i32* unk, i64* rec_bounds) {
    auto* sess = static_cast<Session*>(s);
    if (sess) {
        sess->index_mode = false;  // see nat_verify_input's comment
        sess->records.clear();
    }
    rec_bounds[0] = 0;
    for (i32 i = 0; i < n; i++) {
        ok[i] = run_verify_input(sess, static_cast<NTx*>(txs[i]), n_ins[i],
                                 amounts[i], spk_blob + spk_offs[i],
                                 spk_offs[i + 1] - spk_offs[i], flags[i], mode,
                                 &err[i], &unk[i]);
        rec_bounds[i + 1] = sess ? (i64)sess->records.size() : 0;
    }
}

// ---------------------------------------------------------------------------
// Index-mode batch surface: the session keeps ONE deduped check list
// (`uniq`) and every consumer — lane prep for the device kernel, salted
// cache digests, verdict publication, exact host fallback — reads it in
// place. Python sees only int32 indices; no check bytes ever cross the
// bridge twice. This is the TPU-era CCheckQueue fan-out
// (checkqueue.h:29-163): `n_threads` workers share the session's oracle
// read-only, draw blocks of consecutive inputs from one cursor, each into a
// scratch session of its own, and a serial merge walks the inputs in index
// order whoever interpreted them: uniq order, rec_idx and every per-input
// array equal the single-threaded run's at any thread count and under any
// timing, so lane order is deterministic.

// Interpret inputs [lo, hi) against `sess` (which may be a worker
// scratch whose `oracle` points at the shared session). rec_end[i] and,
// where asked for, uniq_end[i] get the sizes of `sess`'s rec_idx and uniq
// after input i: what i added lies between its predecessor's and its own.
static void run_idx_range(Session* sess, void** txs, const i32* n_ins,
                          const i64* amounts, const u8* spk_blob,
                          const i64* spk_offs, const i32* flags, i32 lo,
                          i32 hi, i32* ok, i32* err, i32* unk, i64* walk,
                          i64* rec_end, i64* uniq_end) {
    for (i32 i = lo; i < hi; i++) {
        ok[i] = run_verify_input(sess, static_cast<NTx*>(txs[i]), n_ins[i],
                                 amounts[i], spk_blob + spk_offs[i],
                                 spk_offs[i + 1] - spk_offs[i], flags[i],
                                 MODE_DEFER, &err[i], &unk[i], &walk[i]);
        rec_end[i] = (i64)sess->rec_idx.size();
        if (uniq_end) uniq_end[i] = (i64)sess->uniq.size();
    }
}

// Blocks a worker draws on average: enough that the last block anyone
// draws is small against the phase (1/32 of a worker's even share where
// inputs cost alike; in index order a long legacy transaction's dear
// digests go first, so its tail is the cheap end), few enough that the
// cursor's atomic add and a transaction changing hands stay unseen. Where
// every worker still draws MIN_DRAWS blocks of them, a block is whole cache
// lines of the per-input arrays (LINE_SLOTS four-byte slots): neighbouring
// blocks belong to different workers, and smaller ones had all of them
// writing every line of ok, err, unk and the merge's marks (3-5 % of the
// workers' summed busy time on the chip's host, PR 47).
constexpr i32 DRAWS_A_WORKER = 32;
constexpr i32 MIN_DRAWS = 8;
constexpr i32 LINE_SLOTS = 16;

void nat_verify_inputs_idx(void* s, void** txs, const i32* n_ins,
                           const i64* amounts, const u8* spk_blob,
                           const i64* spk_offs, const i32* flags, i32 n,
                           i32 n_threads, i32* ok, i32* err, i32* unk,
                           i64* rec_bounds) {
    auto* sess = static_cast<Session*>(s);
    i64 at = steady_ns();
    StageEnd<Session::ST_COUNT> merged{sess->stages, Session::ST_INTERPRET_MERGE, at};
    sess->index_mode = true;
    sess->rec_idx.clear();
    sess->call_walk.assign((size_t)n, 0);
    i64* walk = sess->call_walk.data();
    StorePool& pool = StorePool::get();
    pool.take(sess->uniq, true);
    rec_bounds[0] = 0;
    FanStats* fan = &sess->fans[Session::FAN_INTERPRET];
    if (n_threads < 2 || n < 2 * n_threads) {
        // One worker, the caller: rec_idx was just cleared, so an input's
        // end is its global bound, and there is nothing to merge.
        at = sess->stages.stamp(Session::ST_INTERPRET_SETUP, at);
        fan_out(n, 1, [&](i32, i32 lo, i32 hi) {
            run_idx_range(sess, txs, n_ins, amounts, spk_blob, spk_offs, flags,
                          lo, hi, ok, err, unk, walk, rec_bounds + 1, nullptr);
        }, fan);
        at = sess->stages.stamp(Session::ST_INTERPRET_WORKERS, at);
        return;
    }
    i32 T = n_threads;
    std::vector<Session> scratch((size_t)T);
    for (i32 t = 0; t < T; t++) {
        scratch[t].index_mode = true;
        scratch[t].oracle = sess;
        pool.take(scratch[t].uniq, false);
    }
    // By input: who interpreted it, and its scratch's rec_idx and uniq
    // sizes after it. A worker draws ever later blocks, so its inputs lie
    // in its scratch in index order.
    std::vector<i32> owner((size_t)n);
    std::vector<i64> rec_end((size_t)n), uniq_end((size_t)n);
    i32 block = std::max(1, n / (DRAWS_A_WORKER * T));
    if (n >= LINE_SLOTS * MIN_DRAWS * T)
        block = (block + LINE_SLOTS - 1) / LINE_SLOTS * LINE_SLOTS;
    std::atomic<i32> cursor{0};
    at = sess->stages.stamp(Session::ST_INTERPRET_SETUP, at);
    fan_out(T, T, [&](i32 t, i32, i32) {
        for (;;) {
            i32 lo = cursor.fetch_add(block, std::memory_order_relaxed);
            if (lo >= n) break;
            i32 hi = std::min(n, lo + block);
            std::fill(owner.begin() + lo, owner.begin() + hi, t);
            run_idx_range(&scratch[t], txs, n_ins, amounts, spk_blob,
                          spk_offs, flags, lo, hi, ok, err, unk, walk,
                          rec_end.data(), uniq_end.data());
        }
    }, fan);
    at = sess->stages.stamp(Session::ST_INTERPRET_WORKERS, at);
    for (const Session& sc : scratch) {
        sess->sighash_computed += sc.sighash_computed;
        sess->sighash_reused += sc.sighash_reused;
        for (int k = 0; k < Session::SK_COUNT; k++) {
            sess->sighash_bytes[k] += sc.sighash_bytes[k];
            sess->sighash_ns[k] += sc.sighash_ns[k];
        }
        for (int k = 0; k < LegacyTemplate::EV_COUNT; k++)
            sess->sighash_template[k] += sc.sighash_template[k];
        for (int k = 0; k < Session::TH_COUNT; k++)
            sess->taproot_hashes[k] += sc.taproot_hashes[k];
    }
    // Serial merge in index order. Input i's worker first met the scratch
    // entries between its previous input's uniq mark and i's own: they are
    // interned into the shared session here (a new entry's bytes are copied
    // once, its hash is the scratch's), the pre-recorded CHECKMULTISIG
    // pairings no rec_idx names among them, then i's rec_idx entries are
    // remapped. An entry a worker met at an earlier input was interned
    // there, and one another worker met first was interned at that input
    // if it comes earlier: discovery order is the single-threaded run's.
    std::vector<std::vector<i32>> remap((size_t)T);
    std::vector<i64> rec_at((size_t)T, 0);
    for (i32 t = 0; t < T; t++) remap[(size_t)t].reserve(scratch[t].uniq.size());
    for (i32 i = 0; i < n; i++) {
        size_t t = (size_t)owner[(size_t)i];
        const Session& sc = scratch[t];
        std::vector<i32>& map = remap[t];
        for (size_t j = map.size(); j < (size_t)uniq_end[(size_t)i]; j++)
            map.push_back(sess->uniq.intern(sc.uniq.entries[j].hash,
                                            sc.uniq.view(j),
                                            sc.uniq.entries[j].spec));
        for (i64& j = rec_at[t]; j < rec_end[(size_t)i]; j++)
            sess->rec_idx.push_back(map[(size_t)sc.rec_idx[(size_t)j]]);
        rec_bounds[i + 1] = (i64)sess->rec_idx.size();
    }
    for (Session& sc : scratch) pool.give(std::move(sc.uniq));
}

// Bytes of retired check stores parked for the next session (StorePool).
i64 nat_store_pool_bytes() {
    StorePool& pool = StorePool::get();
    std::lock_guard<std::mutex> lock(pool.mu);
    return (i64)pool.bytes;
}

i32 nat_session_uniq_count(void* s) {
    return (i32)static_cast<Session*>(s)->uniq.size();
}

// Pre-recorded CHECKMULTISIG pairings that became uniq entries of this
// session so far (CheckStore::spec_entries; index mode).
i64 nat_session_spec_pairings(void* s) {
    return static_cast<Session*>(s)->uniq.spec_entries;
}

// (signature, key) pairings the CHECKMULTISIG cursor walks tried in each
// interpretation of the session's newest verify call, by the input's
// position in that call (one entry after nat_verify_input). Copies at most
// `capacity` entries; returns the count copied.
i64 nat_session_call_walks(void* s, i64* out, i64 capacity) {
    const auto& w = static_cast<Session*>(s)->call_walk;
    i64 n = std::min((i64)w.size(), capacity);
    if (n > 0) std::memcpy(out, w.data(), (size_t)n * sizeof(i64));
    return n;
}

// ECDSA message digests this session's interpretations have hashed
// (out[0]) and read again from a CHECKMULTISIG's record of its signatures
// (out[1]) so far.
void nat_session_sighashes(void* s, i64* out) {
    auto* sess = static_cast<Session*>(s);
    out[0] = sess->sighash_computed;
    out[1] = sess->sighash_reused;
}

// What the digests of out[0] above cost so far, by kind: out[0], out[1] the
// bytes fed to SHA-256 for legacy and BIP 143 digests, out[2], out[3] the
// nanoseconds of thread time spent on them, the legacy template's build
// included; out[4], out[5], out[6] the legacy templates built, the digests
// hashed from one, and those of them resumed from a grid point.
void nat_session_sighash_work(void* s, i64* out) {
    auto* sess = static_cast<Session*>(s);
    for (int k = 0; k < Session::SK_COUNT; k++) {
        out[k] = sess->sighash_bytes[k];
        out[Session::SK_COUNT + k] = sess->sighash_ns[k];
    }
    for (int k = 0; k < LegacyTemplate::EV_COUNT; k++)
        out[2 * Session::SK_COUNT + k] = sess->sighash_template[k];
}

// The session's native stage clock so far (interp.hpp Session::stages,
// Session::fans), nanoseconds and counts. out[0..6): the stages' nanoseconds
// in the enum's order (interpret setup, workers, merge; lanes order, shards;
// digests shards); out[6..12): the times each was stamped; out[12..30): the
// three fan-outs' accounts (interpret, lanes, digests), six each (wall,
// held, sum, max, start_lag, tail).
void nat_session_stages(void* s, i64* out) {
    auto* sess = static_cast<Session*>(s);
    sess->stages.read(out);
    i64* fan = out + 2 * Session::ST_COUNT;
    for (int c = 0; c < Session::FAN_COUNT; c++)
        std::memcpy(fan + c * FanStats::COUNT, sess->fans[c].ns, sizeof sess->fans[c].ns);
}

// 1 where SHA-256 runs on the CPU's SHA extensions, 0 on the generic transform.
i32 nat_sha256_uses_sha_ni() {
#ifdef NAT_SHA_NI_POSSIBLE
    return sha_ni_available() ? 1 : 0;
#else
    return 0;
#endif
}

// Lanes nat_session_uniq_lanes has prepped out of this session so far, by
// kind: out[0] ecdsa, out[1] schnorr, out[2] tweak.
void nat_session_lane_kinds(void* s, i64* out) {
    auto* sess = static_cast<Session*>(s);
    for (int k = 0; k < 3; k++) out[k] = sess->lanes_by_kind[k];
}

// Taproot hashes this session's interpretations have made so far: out[0]
// BIP 341 digests, out[1] TapLeaf, out[2] TapBranch, out[3] TapTweak.
void nat_session_taproot_hashes(void* s, i64* out) {
    auto* sess = static_cast<Session*>(s);
    for (int k = 0; k < Session::TH_COUNT; k++) out[k] = sess->taproot_hashes[k];
}

// A stale or negative uniq index from the driver is an OOB read / heap
// corruption; fail loudly instead (same pattern as digest_one's kind
// guard).
inline void uniq_guard(const Session* sess, i32 idx) {
    if (idx < 0 || (size_t)idx >= sess->uniq.size()) {
        std::fprintf(stderr, "uniq_at: index %d out of range (uniq size %zu)\n",
                     idx, sess->uniq.size());
        std::abort();
    }
}

// Entry idx's parts, in place (a view into the session's arena).
inline PartsView uniq_at(const Session* sess, i32 idx) {
    uniq_guard(sess, idx);
    return sess->uniq.view((size_t)idx);
}

// `capacity` is the caller's buffer size (the rec_idx length observed at
// verify time); ctypes releases the GIL during calls, so copying
// rec_idx.size() entries unchecked would overflow the buffer if another
// thread grew the session in between. Returns the count actually copied.
i64 nat_session_recidx_data(void* s, i32* out, i64 capacity) {
    auto* sess = static_cast<Session*>(s);
    i64 n = (i64)sess->rec_idx.size();
    if (capacity < n) n = capacity;
    if (n > 0) std::memcpy(out, sess->rec_idx.data(), (size_t)n * sizeof(i32));
    return n;
}

// How many workers nat_session_uniq_lanes / _digests use for `n` entries
// (1: the serial path, no thread made) — for the driver's counter.
i32 nat_prep_shards(i32 n, i32 n_threads) { return prep_shards(n, n_threads); }

// Kernel lanes for uniq[idxs[0..nidx)] — session-resident prep, no wire
// blob. Output layout identical to nat_prep_lanes. Sharded over up to
// `n_threads` workers by lane count (prep_shards); the session is only
// read.
void nat_session_uniq_lanes(void* s, const i32* idxs, i32 nidx, i32 n_threads,
                            u8* fields, i32* want_odd, i32* parity,
                            i32* has_t2, i32* neg1, i32* neg2, i32* valid) {
    auto* sess = static_cast<Session*>(s);
    i64 at = steady_ns();
    std::vector<PartsView> parts;
    parts.reserve((size_t)nidx);
    for (i32 j = 0; j < nidx; j++) {
        parts.push_back(lanes_order(uniq_at(sess, idxs[j])));
        int kind = parts.back().kind;
        if (kind >= KIND_ECDSA && kind <= KIND_TWEAK) sess->lanes_by_kind[kind]++;
    }
    at = sess->stages.stamp(Session::ST_LANES_ORDER, at);
    prep_lanes_impl(parts, n_threads, fields, want_odd, parity, has_t2, neg1,
                    neg2, valid, &sess->fans[Session::FAN_LANES]);
    sess->stages.stamp(Session::ST_LANES_SHARDS, at);
}

// Salted cache-key digests for uniq[idxs[0..nidx)] (models/sigcache.py
// key stream — same bytes nat_digest_checks produces for the wire shape).
// A digest an entry, independent: sharded as nat_session_uniq_lanes is.
void nat_session_uniq_digests(void* s, const u8* salt, i64 salt_len,
                              const i32* idxs, i32 nidx, i32 n_threads,
                              u8* out) {
    auto* sess = static_cast<Session*>(s);
    i64 at = steady_ns();
    fan_out(nidx, prep_shards(nidx, n_threads), [&](i32, i32 lo, i32 hi) {
        for (i32 j = lo; j < hi; j++)
            digest_one(salt, salt_len, uniq_at(sess, idxs[j]),
                       out + 32 * (size_t)j);
    }, &sess->fans[Session::FAN_DIGESTS]);
    sess->stages.stamp(Session::ST_DIGESTS_SHARDS, at);
}

// Publish device/cache verdicts for uniq[idxs[0..nidx)] into the oracle:
// one guarded store an entry.
void nat_session_publish_uniq(void* s, const i32* idxs, i32 nidx,
                              const i32* results) {
    auto* sess = static_cast<Session*>(s);
    for (i32 j = 0; j < nidx; j++) {
        uniq_guard(sess, idxs[j]);
        sess->uniq.entries[(size_t)idxs[j]].verdict =
            results[j] != 0 ? CheckStore::V_TRUE : CheckStore::V_FALSE;
    }
}

// Exact host verdict for one uniq entry (the exceptional-lane fixup path:
// crafted scalar collisions the fast device adds defer — never honest
// traffic).
i32 nat_session_uniq_host_verify(void* s, i32 idx) {
    auto* sess = static_cast<Session*>(s);
    PartsView v = uniq_at(sess, idx);
    if (v.kind == KIND_ECDSA)
        return verify_ecdsa(v.p0, (size_t)v.l0, v.p1, (size_t)v.l1, v.p2) ? 1
                                                                           : 0;
    if (v.kind == KIND_SCHNORR) return verify_schnorr(v.p0, v.p1, v.p2) ? 1 : 0;
    // tweak record order: q32 | internal32 | tweak32
    return tweak_add_check(v.p0, v.parity, v.p1, v.p2) ? 1 : 0;
}

}  // extern "C"

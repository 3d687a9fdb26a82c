// Native block layer: block codec, merkle (CVE-2012-2459), PoW, the
// context-free CheckBlock rules, witness commitment, sigop costing, a
// UTXO view and the ConnectBlock accounting pass.
//
// Twin of bitcoinconsensus_tpu/core/block.py + core/tx_check.py +
// models/validate.py (which mirror the reference's validation.cpp:3402-3474
// CheckBlock, consensus/merkle.cpp:45-84, pow.cpp:74-90,
// consensus/tx_verify.cpp:125-218 and validation.cpp:1946-2228
// ConnectBlock). The Python layer stays the executable spec; byte/verdict
// equality is asserted by tests/test_native_block.py. Reject reasons are
// integer codes here; bitcoinconsensus_tpu/native_bridge.py maps them to
// the reference's reason strings.
#pragma once

#include "interp.hpp"

#include <algorithm>
#include <array>
#include <memory>
#include <type_traits>
#include <unordered_map>

namespace nat {

constexpr i64 BLK_MAX_WEIGHT = 4'000'000;        // consensus.h:14
constexpr i64 BLK_WITNESS_SCALE = 4;             // consensus.h:21
constexpr i64 BLK_MAX_SIGOPS_COST = 80'000;      // consensus.h:17
constexpr i64 BLK_MAX_MONEY = 21'000'000LL * 100'000'000LL;
constexpr int BLK_COINBASE_MATURITY = 100;       // consensus.h:19
constexpr i64 BLK_HALVING_INTERVAL = 210'000;    // chainparams.cpp mainnet
constexpr int MAX_PUBKEYS_PER_MULTISIG_N = 20;   // script.h:33
constexpr size_t MIN_WITNESS_COMMITMENT_N = 38;  // validation.h:19

// Reject reasons as stable integer codes; the bridge's REASONS table maps
// them to the exact reference strings (order is part of the ABI).
enum BlkReason : i32 {
    BR_OK = 0,
    BR_HIGH_HASH,
    BR_BAD_MERKLE,
    BR_DUPLICATE,
    BR_BAD_LENGTH,
    BR_CB_MISSING,
    BR_CB_MULTIPLE,
    BR_VIN_EMPTY,
    BR_VOUT_EMPTY,
    BR_OVERSIZE,
    BR_VOUT_NEGATIVE,
    BR_VOUT_TOOLARGE,
    BR_TXOUTTOTAL_TOOLARGE,
    BR_INPUTS_DUPLICATE,
    BR_CB_LENGTH,
    BR_PREVOUT_NULL,
    BR_BLK_SIGOPS,
    BR_WITNESS_NONCE_SIZE,
    BR_WITNESS_MERKLE_MATCH,
    BR_UNEXPECTED_WITNESS,
    BR_BIP30,
    BR_INPUTS_MISSINGORSPENT,
    BR_PREMATURE_COINBASE,
    BR_INPUTVALUES_OUTOFRANGE,
    BR_IN_BELOWOUT,
    BR_FEE_OUTOFRANGE,
    BR_CB_AMOUNT,
    BR_DESERIALIZE,
};

using Hash32 = std::array<u8, 32>;

// A coin's key, everywhere a coin is keyed (the view, a block's own table,
// the undo record, the duplicate-input check): the 36 bytes txid[32] || n
// little-endian, held inline. The view's digest hashes exactly these bytes.
struct NOutPoint {
    u8 b[36];

    NOutPoint(const u8 txid[32], u32 n) {
        std::memcpy(b, txid, 32);
        for (int j = 0; j < 4; j++) b[32 + j] = u8(n >> (8 * j));
    }
    bool operator==(const NOutPoint& o) const {
        return std::memcmp(b, o.b, sizeof b) == 0;
    }
    bool operator<(const NOutPoint& o) const {
        return std::memcmp(b, o.b, sizeof b) < 0;
    }
};
static_assert(std::is_trivially_copyable<NOutPoint>::value &&
                  sizeof(NOutPoint) == 36,
              "a coin's key is 36 plain bytes");

// The hash of every coin table (coins.h SaltedOutpointHasher): SipHash-2-4
// of the 36 outpoint bytes under two 64-bit keys drawn once a process, so
// that whoever picks the txids cannot pick the buckets. Never a slice of
// the txid: a miner grinds those.
struct OutpointHasher {
    u64 k0, k1;

    OutpointHasher() {
        static const std::array<u64, 2> salt = [] {
            std::random_device rd;
            auto word = [&rd] { return ((u64)rd() << 32) | (u64)rd(); };
            return std::array<u64, 2>{word(), word()};
        }();
        k0 = salt[0];
        k1 = salt[1];
    }

    static u64 rotl(u64 x, int r) { return (x << r) | (x >> (64 - r)); }

    u64 operator()(const u8 txid[32], u32 n) const {
        u64 v0 = 0x736f6d6570736575ULL ^ k0, v1 = 0x646f72616e646f6dULL ^ k1;
        u64 v2 = 0x6c7967656e657261ULL ^ k0, v3 = 0x7465646279746573ULL ^ k1;
        auto round = [&] {
            v0 += v1; v1 = rotl(v1, 13); v1 ^= v0; v0 = rotl(v0, 32);
            v2 += v3; v3 = rotl(v3, 16); v3 ^= v2;
            v0 += v3; v3 = rotl(v3, 21); v3 ^= v0;
            v2 += v1; v1 = rotl(v1, 17); v1 ^= v2; v2 = rotl(v2, 32);
        };
        auto absorb = [&](u64 m) {
            v3 ^= m;
            round();
            round();
            v0 ^= m;
        };
        for (int w = 0; w < 4; w++) {
            u64 m = 0;
            for (int j = 0; j < 8; j++) m |= (u64)txid[8 * w + j] << (8 * j);
            absorb(m);
        }
        absorb(((u64)36 << 56) | n);  // the last 4 bytes under the length
        v2 ^= 0xff;
        for (int i = 0; i < 4; i++) round();
        return v0 ^ v1 ^ v2 ^ v3;
    }
    // Not noexcept, on purpose: libstdc++ then keeps each node's hash code
    // beside it, and a probe walks a bucket without hashing its nodes again.
    size_t operator()(const NOutPoint& k) const {
        u32 n = 0;
        for (int j = 0; j < 4; j++) n |= (u32)k.b[32 + j] << (8 * j);
        return (size_t)(*this)(k.b, n);
    }
};

inline bool tx_is_coinbase(const NTx& tx) {
    if (tx.vin.size() != 1) return false;
    const NTxIn& in = tx.vin[0];
    if (in.prevout_n != 0xFFFFFFFFu) return false;
    for (int i = 0; i < 32; i++)
        if (in.prevout_hash[i]) return false;
    return true;
}

// ConnectBlock accounting result (filled by block_accounting below): one
// entry per non-coinbase input, in block order.
struct BlockAcct {
    bool ready = false;
    i64 fees = 0;
    i64 sigop_cost = 0;
    std::vector<i32> tx_index;   // which vtx
    std::vector<i32> n_in;       // which input of that tx
    std::vector<i64> amounts;    // spent-output value per input
    std::vector<i64> spk_offs;   // n_inputs+1 offsets into spk_blob
    Bytes spk_blob;              // spent-output scriptPubKeys
    std::vector<Hash32> spent_digests;  // per tx (coinbase rows zero)
    Bytes script_keys;  // 32 an input: script-execution-cache keys, if asked
};

struct NBlock {
    i32 version;
    u8 prev_hash[32];
    u8 merkle[32];
    u32 time_, bits, nonce;
    u8 header_hash[32];  // sha256d over the 80 header bytes, wire order
    std::vector<std::unique_ptr<NTx>> vtx;
    std::vector<Hash32> txids;   // sha256d(serialize(false)), wire order
    std::vector<Hash32> wtxids;  // sha256d(serialize(true))
    std::vector<i64> nowit_size;  // per-tx no-witness serialized size
    i64 ser_size = 0;
    BlockAcct acct;
    // Hash-table probes (a find, an insert or an erase by key) the last
    // accounting of this block and the applies since made: of the view, and
    // of the block's own coin table. Pass 1 starts both at zero; `mutable`
    // because an apply reads the block and counts on it.
    mutable i64 view_probes = 0, block_probes = 0;
    // The native stage clock of the `accounting` phase (interp.hpp): pass 1
    // and pass 2 inside block_accounting, and nat_block_acct_data's copy of
    // the five arrays out. Pass 1 starts all three at zero.
    enum : int { ST_DECIDE = 0, ST_FILL, ST_COPY, ST_COUNT };
    StageClock<ST_COUNT> stages;
};

// The parse's per-transaction stage, from the block's own wire bytes
// `data` (what the scan read; no tx is serialized again): wtxid = sha256d
// of the tx's span; a tx read without the witness marker has that digest
// for its txid too, any other hashes version || vin count .. last output
// || locktime. Allocates nothing, cannot throw.
inline void block_hash_txs(NBlock& blk, const u8* data) {
    for (size_t t = 0; t < blk.vtx.size(); t++) {
        const NTx& tx = *blk.vtx[t];
        const u8* span = data + tx.span_lo;
        size_t n = (size_t)tx.ser_size;
        sha256d(span, n, blk.wtxids[t].data());
        if (tx.body_lo == tx.span_lo + 4) {
            blk.txids[t] = blk.wtxids[t];
            blk.nowit_size[t] = tx.ser_size;
            continue;
        }
        size_t body = tx.body_hi - tx.body_lo;
        u8 once[32];
        Sha256 h;
        h.write(span, 4);
        h.write(data + tx.body_lo, body);
        h.write(span + n - 4, 4);
        h.finalize(once);
        sha256(once, 32, blk.txids[t].data());
        blk.nowit_size[t] = (i64)(body + 8);
    }
}

// Block wire parse (primitives/block.h:75-90 / core/block.py
// Block.deserialize): 80-byte header + compact count + txs; trailing
// bytes reject. Throws SerErr. The scan builds the txs; block_hash_txs
// then fills the per-tx rows (txids, wtxids, nowit_size) from the wire
// bytes themselves.
inline NBlock* block_parse(const u8* data, size_t len) {
    Reader r(data, len);
    auto blk = std::make_unique<NBlock>();
    const u8* hdr = r.read(80);
    sha256d(hdr, 80, blk->header_hash);
    {
        Reader hr(hdr, 80);
        blk->version = hr.read_i32();
        std::memcpy(blk->prev_hash, hr.read(32), 32);
        std::memcpy(blk->merkle, hr.read(32), 32);
        blk->time_ = hr.read_u32();
        blk->bits = hr.read_u32();
        blk->nonce = hr.read_u32();
    }
    u64 n = r.read_compact_size();
    for (u64 i = 0; i < n; i++)
        blk->vtx.emplace_back(tx_parse_from(r));
    if (r.pos != r.len) throw SerErr("trailing data after block");
    blk->ser_size = (i64)len;
    blk->txids.resize(blk->vtx.size());
    blk->wtxids.resize(blk->vtx.size());
    blk->nowit_size.resize(blk->vtx.size());
    block_hash_txs(*blk, data);
    return blk.release();
}

// Merkle root with mutation detection (consensus/merkle.cpp:45-64):
// sibling equality is checked BEFORE duplicating the odd tail, so the
// synthetic last pair never counts as mutation.
inline void merkle_root(std::vector<Hash32> level, u8 out[32], bool* mutated) {
    *mutated = false;
    if (level.empty()) {
        std::memset(out, 0, 32);
        return;
    }
    while (level.size() > 1) {
        for (size_t pos = 0; pos + 1 < level.size(); pos += 2)
            if (level[pos] == level[pos + 1]) *mutated = true;
        if (level.size() & 1) level.push_back(level.back());
        std::vector<Hash32> next(level.size() / 2);
        for (size_t i = 0; i < level.size(); i += 2) {
            u8 buf[64];
            std::memcpy(buf, level[i].data(), 32);
            std::memcpy(buf + 32, level[i + 1].data(), 32);
            sha256d(buf, 64, next[i / 2].data());
        }
        level = std::move(next);
    }
    std::memcpy(out, level[0].data(), 32);
}

// Compact bits -> 32-byte big-endian target (arith_uint256 SetCompact).
inline void bits_to_target_be(u32 bits, u8 out_be[32], bool* negative,
                              bool* overflow) {
    std::memset(out_be, 0, 32);
    u32 size = bits >> 24;
    u32 word = bits & 0x007FFFFF;
    *negative = word != 0 && (bits & 0x00800000) != 0;
    *overflow = word != 0 && (size > 34 || (word > 0xFF && size > 33) ||
                              (word > 0xFFFF && size > 32));
    if (*overflow) return;
    if (size <= 3) {
        word >>= 8 * (3 - size);
        out_be[29] = u8(word >> 16);
        out_be[30] = u8(word >> 8);
        out_be[31] = u8(word);
    } else {
        // value = word * 256^(size-3): word's 3 bytes end (8*(size-3))
        // bytes above the bottom.
        for (int i = 0; i < 3; i++) {
            int pos = 31 - (int)(size - 3) - i;  // i=0 -> lowest word byte
            if (pos >= 0 && pos < 32) out_be[pos] = u8(word >> (8 * i));
        }
    }
}

inline int cmp_be(const u8 a[32], const u8 b[32]) {
    return std::memcmp(a, b, 32);
}

inline bool be_is_zero(const u8 a[32]) {
    for (int i = 0; i < 32; i++)
        if (a[i]) return false;
    return true;
}

// CheckProofOfWork (pow.cpp:74-90); header hash arrives wire (LE) order,
// pow_limit as 32 big-endian bytes.
inline bool check_pow(const u8 header_hash[32], u32 bits,
                      const u8 pow_limit_be[32]) {
    u8 target[32];
    bool neg, over;
    bits_to_target_be(bits, target, &neg, &over);
    if (neg || be_is_zero(target) || over) return false;
    if (cmp_be(target, pow_limit_be) > 0) return false;
    u8 hash_be[32];
    for (int i = 0; i < 32; i++) hash_be[i] = header_hash[31 - i];
    return cmp_be(hash_be, target) <= 0;
}

// Legacy sigop counting (script.cpp:153-177 / core/script.py
// get_sig_op_count).
inline i64 sig_op_count(const Bytes& script, bool accurate) {
    i64 n = 0;
    int last_opcode = 0xFF;  // OP_INVALIDOPCODE
    Span sp = span_of(script);
    size_t pos = 0;
    while (pos < sp.size()) {
        int opcode;
        const u8* d;
        size_t dl;
        if (!decode_op(sp, pos, opcode, &d, &dl)) break;
        if (opcode == OP_CHECKSIG || opcode == OP_CHECKSIGVERIFY) {
            n += 1;
        } else if (opcode == OP_CHECKMULTISIG ||
                   opcode == OP_CHECKMULTISIGVERIFY) {
            if (accurate && last_opcode >= OP_1 && last_opcode <= OP_16)
                n += last_opcode - OP_1 + 1;
            else
                n += MAX_PUBKEYS_PER_MULTISIG_N;
        }
        last_opcode = opcode;
    }
    return n;
}

// WitnessSigOps (interpreter.cpp:2058-2072).
inline i64 witness_sig_ops(int version, const Bytes& program,
                           const std::vector<Bytes>& witness) {
    if (version == 0) {
        if (program.size() == 20) return 1;
        if (program.size() == 32 && !witness.empty())
            return sig_op_count(witness.back(), true);
    }
    return 0;
}

// Last push of a push-only scriptSig (the P2SH redeem script).
inline Bytes last_push(const Bytes& script) {
    Bytes data;
    Span sp = span_of(script);
    size_t pos = 0;
    while (pos < sp.size()) {
        int opcode;
        const u8* d;
        size_t dl;
        if (!decode_op(sp, pos, opcode, &d, &dl)) break;
        data.assign(d ? d : (const u8*)"", d ? d + dl : (const u8*)"");
    }
    return data;
}

// CountWitnessSigOps (interpreter.cpp:2074-2103).
inline i64 count_witness_sigops(const Bytes& script_sig, const Bytes& spk,
                                const std::vector<Bytes>& witness, u32 flags) {
    if (!(flags & F_WITNESS)) return 0;
    int version;
    Bytes program;
    if (is_witness_program(spk, &version, &program))
        return witness_sig_ops(version, program, witness);
    if (is_p2sh(spk) && is_push_only(script_sig)) {
        Bytes redeem = last_push(script_sig);
        if (is_witness_program(redeem, &version, &program))
            return witness_sig_ops(version, program, witness);
    }
    return 0;
}

// GetTransactionSigOpCost (consensus/tx_verify.cpp:125-147). `spent` must
// be one output per input for non-coinbase txs.
inline i64 tx_sigop_cost(const NTx& tx, const std::vector<const NTxOut*>& spent,
                         u32 flags) {
    i64 cost = 0;
    for (const auto& in : tx.vin) cost += sig_op_count(in.script_sig, false);
    for (const auto& out : tx.vout) cost += sig_op_count(out.spk, false);
    cost *= BLK_WITNESS_SCALE;
    if (tx_is_coinbase(tx)) return cost;
    if (flags & F_P2SH) {
        i64 p2sh = 0;
        for (size_t i = 0; i < tx.vin.size(); i++) {
            if (is_p2sh(spent[i]->spk) && is_push_only(tx.vin[i].script_sig))
                p2sh += sig_op_count(last_push(tx.vin[i].script_sig), true);
        }
        cost += p2sh * BLK_WITNESS_SCALE;
    }
    for (size_t i = 0; i < tx.vin.size(); i++)
        cost += count_witness_sigops(tx.vin[i].script_sig, spent[i]->spk,
                                     tx.vin[i].witness, flags);
    return cost;
}

// A transaction's outputs summed as CheckTransaction sums them: every term
// and every partial sum inside MoneyRange, so the i64 never overflows.
// Returns BR_OK with the sum in `total`, else the reject.
inline i32 sum_outputs(const NTx& tx, i64& total) {
    total = 0;
    for (const auto& out : tx.vout) {
        if (out.value < 0) return BR_VOUT_NEGATIVE;
        if (out.value > BLK_MAX_MONEY) return BR_VOUT_TOOLARGE;
        total += out.value;
        if (total > BLK_MAX_MONEY) return BR_TXOUTTOTAL_TOOLARGE;
    }
    return BR_OK;
}

// CheckTransaction (consensus/tx_verify.cpp:157-196 / core/tx_check.py).
inline i32 check_transaction(const NTx& tx, i64 nowit_size) {
    if (tx.vin.empty()) return BR_VIN_EMPTY;
    if (tx.vout.empty()) return BR_VOUT_EMPTY;
    if (nowit_size * BLK_WITNESS_SCALE > BLK_MAX_WEIGHT) return BR_OVERSIZE;
    i64 value_out;
    if (i32 r = sum_outputs(tx, value_out)) return r;
    if (tx.vin.size() > 1) {
        // Duplicate inputs: the outpoints sorted, equal neighbours looked
        // for (the reference's std::set<COutPoint>, in one allocation).
        std::vector<NOutPoint> seen;
        seen.reserve(tx.vin.size());
        for (const auto& in : tx.vin)
            seen.emplace_back(in.prevout_hash, in.prevout_n);
        std::sort(seen.begin(), seen.end());
        if (std::adjacent_find(seen.begin(), seen.end()) != seen.end())
            return BR_INPUTS_DUPLICATE;
    }
    if (tx_is_coinbase(tx)) {
        size_t n = tx.vin[0].script_sig.size();
        if (n < 2 || n > 100) return BR_CB_LENGTH;
    } else {
        for (const auto& in : tx.vin) {
            bool null_hash = true;
            for (int i = 0; i < 32; i++)
                if (in.prevout_hash[i]) null_hash = false;
            if (null_hash && in.prevout_n == 0xFFFFFFFFu)
                return BR_PREVOUT_NULL;
        }
    }
    return BR_OK;
}

// Witness-commitment rules (validation.cpp:3385-3428 / core/block.py
// check_witness_commitment).
inline i32 check_witness_commitment(const NBlock& blk) {
    int commitpos = -1;
    if (!blk.vtx.empty()) {
        const NTx& cb = *blk.vtx[0];
        for (size_t o = 0; o < cb.vout.size(); o++) {
            const Bytes& spk = cb.vout[o].spk;
            if (spk.size() >= MIN_WITNESS_COMMITMENT_N && spk[0] == OP_RETURN &&
                spk[1] == 0x24 && spk[2] == 0xAA && spk[3] == 0x21 &&
                spk[4] == 0xA9 && spk[5] == 0xED)
                commitpos = (int)o;
        }
    }
    if (commitpos != -1) {
        const NTx& cb = *blk.vtx[0];
        if (cb.vin.empty()) return BR_WITNESS_NONCE_SIZE;
        const auto& witness = cb.vin[0].witness;
        if (witness.size() != 1 || witness[0].size() != 32)
            return BR_WITNESS_NONCE_SIZE;
        // Witness merkle root: coinbase wtxid pinned to zero
        // (consensus/merkle.cpp:75-84).
        std::vector<Hash32> leaves(blk.vtx.size());
        leaves[0].fill(0);
        for (size_t i = 1; i < blk.vtx.size(); i++) leaves[i] = blk.wtxids[i];
        u8 root[32];
        bool mut_;
        merkle_root(std::move(leaves), root, &mut_);
        u8 buf[64], expect[32];
        std::memcpy(buf, root, 32);
        std::memcpy(buf + 32, witness[0].data(), 32);
        sha256d(buf, 64, expect);
        if (std::memcmp(expect, cb.vout[commitpos].spk.data() + 6, 32) != 0)
            return BR_WITNESS_MERKLE_MATCH;
        return BR_OK;
    }
    for (const auto& tx : blk.vtx)
        if (tx->has_witness()) return BR_UNEXPECTED_WITNESS;
    return BR_OK;
}

// Context-free CheckBlock (validation.cpp:3402-3474 / core/block.py
// check_block). `pow_limit_be`: 32 big-endian bytes.
inline i32 check_block(const NBlock& blk, bool do_pow,
                       const u8 pow_limit_be[32], bool do_merkle) {
    if (do_pow && !check_pow(blk.header_hash, blk.bits, pow_limit_be))
        return BR_HIGH_HASH;
    if (do_merkle) {
        u8 root[32];
        bool mutated;
        merkle_root(blk.txids, root, &mutated);
        if (std::memcmp(blk.merkle, root, 32) != 0) return BR_BAD_MERKLE;
        if (mutated) return BR_DUPLICATE;
    }
    i64 nowit_total = 80;
    {
        Bytes cs;
        put_compact_size(cs, blk.vtx.size());
        nowit_total += (i64)cs.size();
    }
    for (i64 s : blk.nowit_size) nowit_total += s;
    if (blk.vtx.empty() ||
        (i64)blk.vtx.size() * BLK_WITNESS_SCALE > BLK_MAX_WEIGHT ||
        nowit_total * BLK_WITNESS_SCALE > BLK_MAX_WEIGHT)
        return BR_BAD_LENGTH;
    if (!tx_is_coinbase(*blk.vtx[0])) return BR_CB_MISSING;
    for (size_t i = 1; i < blk.vtx.size(); i++)
        if (tx_is_coinbase(*blk.vtx[i])) return BR_CB_MULTIPLE;
    for (size_t i = 0; i < blk.vtx.size(); i++) {
        i32 r = check_transaction(*blk.vtx[i], blk.nowit_size[i]);
        if (r != BR_OK) return r;
    }
    i64 sigops = 0;
    for (const auto& tx : blk.vtx) {
        for (const auto& in : tx->vin) sigops += sig_op_count(in.script_sig, false);
        for (const auto& out : tx->vout) sigops += sig_op_count(out.spk, false);
    }
    if (sigops * BLK_WITNESS_SCALE > BLK_MAX_SIGOPS_COST) return BR_BLK_SIGOPS;
    return BR_OK;
}

// --------------------------------------------------------------------------
// UTXO view (coins.h CCoinsViewCache role, dict-backed like
// models/validate.py CoinsView).

struct NCoin {
    i64 value;
    Bytes spk;
    i32 height;
    bool coinbase;
};

struct NView {
    std::unordered_map<NOutPoint, NCoin, OutpointHasher> map;

    static NOutPoint key(const u8 txid[32], u32 n) { return NOutPoint(txid, n); }
};

inline i64 blk_subsidy(i64 height) {
    i64 halvings = height / BLK_HALVING_INTERVAL;
    if (halvings >= 64) return 0;
    return (50 * 100'000'000LL) >> halvings;
}

// ConnectBlock's accounting phases (validation.cpp:2155-2228 /
// models/validate.py phase 2 + coinbase cap), in two passes. Neither
// mutates the view.
//
// Pass 1, block_acct_decide, holds everything that can fail, in block
// order: the BIP30 scan, input existence, maturity and value rules, fees,
// the sigop budget, the coinbase cap. It gathers each tx's spent outputs
// once (`spent`, per tx, one an input) and sizes blk.acct's per-input
// arrays. A block it refuses is left as it was but for a cleared blk.acct.
//
// Pass 2, block_acct_fill, only hashes and fills: the per-input records,
// the spent-output digest (models/sigcache.py spent_digest stream), each
// tx's hash precompute with its spent outputs moved in (the script phase
// needs them) and, given a salt, the script-execution-cache key of every
// input. Allocates nothing, cannot throw.
using SpentOutputs = std::vector<std::vector<NTxOut>>;

// Pass 1's one table of the block's own coins: every outpoint an input of
// the block has named (`spent`) and every output the block has created so
// far, which it points at in the parsed block (`out`; null for a coin of
// the view). Open addressing over slots that borrow their key's txid from
// the block, sized once for the block's inputs and outputs at a load of a
// half at most: one allocation a block, none a coin.
struct BlockCoins {
    struct Slot {
        const u8* txid = nullptr;  // null: empty
        u32 n = 0;
        u32 tag = 0;  // the hash's upper half: most strangers differ here
        const NTxOut* out = nullptr;
        bool coinbase = false;
        bool spent = false;
    };
    std::vector<Slot> slots;
    OutpointHasher hasher;

    explicit BlockCoins(size_t n_keys) {
        size_t cap = 16;
        while (cap < 2 * n_keys) cap *= 2;
        slots.resize(cap);
    }

    // The key's slot: its own, or the empty one it now takes (nothing
    // spent, nothing made).
    Slot& probe(const u8 txid[32], u32 n) {
        u64 h = hasher(txid, n);
        u32 tag = (u32)(h >> 32);
        size_t mask = slots.size() - 1;
        for (size_t i = (size_t)h & mask;; i = (i + 1) & mask) {
            Slot& s = slots[i];
            if (!s.txid) {
                s.txid = txid;
                s.n = n;
                s.tag = tag;
                return s;
            }
            if (s.tag == tag && s.n == n && std::memcmp(s.txid, txid, 32) == 0)
                return s;
        }
    }
};

inline i32 block_acct_decide(NBlock& blk, const NView& view, i64 height,
                             u32 flags, bool with_keys, SpentOutputs& all) {
    BlockAcct& A = blk.acct;
    A = BlockAcct();
    blk.view_probes = blk.block_probes = 0;
    // The production driver runs check_block first (which rejects empty
    // blocks with bad-blk-length), but this entry is independently
    // reachable through the C ABI — the coinbase-cap read below must not
    // index an empty vtx (found by fuzz/fuzz_nat.cpp on its seed corpus).
    if (blk.vtx.empty()) return BR_BAD_LENGTH;
    size_t n_tx = blk.vtx.size();

    // BIP30 against the start-of-block view.
    size_t n_keys = 0;
    for (size_t t = 0; t < n_tx; t++) {
        const NTx& tx = *blk.vtx[t];
        n_keys += tx.vout.size() + (tx_is_coinbase(tx) ? 0 : tx.vin.size());
        for (u32 n = 0; n < tx.vout.size(); n++) {
            blk.view_probes++;
            if (view.map.count(NView::key(blk.txids[t].data(), n)))
                return BR_BIP30;
        }
    }

    BlockCoins coins(n_keys);
    all.assign(n_tx, {});
    size_t n_in = 0, spk_bytes = 0;
    std::vector<const NTxOut*> sp;
    for (size_t t = 0; t < n_tx; t++) {
        const NTx& tx = *blk.vtx[t];
        bool cb = tx_is_coinbase(tx);
        std::vector<NTxOut>& spent = all[t];
        if (!cb) {
            spent.reserve(tx.vin.size());
            i64 value_in = 0;
            for (const auto& in : tx.vin) {
                // One probe says whether the block spent this coin before
                // (a reject, before any look-up) and whether it made it.
                blk.block_probes++;
                BlockCoins::Slot& own =
                    coins.probe(in.prevout_hash, in.prevout_n);
                if (own.spent) return BR_INPUTS_MISSINGORSPENT;
                own.spent = true;
                const NTxOut* out = own.out;
                i32 coin_height = (i32)height;
                bool coin_cb = own.coinbase;
                if (!out) {  // not of this block: the view's, or nobody's
                    blk.view_probes++;
                    auto itv = view.map.find(
                        NView::key(in.prevout_hash, in.prevout_n));
                    if (itv == view.map.end())
                        return BR_INPUTS_MISSINGORSPENT;
                    const NCoin& coin = itv->second;
                    coin_height = coin.height;
                    coin_cb = coin.coinbase;
                    spent.push_back(NTxOut{coin.value, coin.spk});
                } else {
                    spent.push_back(*out);
                }
                const NTxOut& got = spent.back();
                if (coin_cb && height - coin_height < BLK_COINBASE_MATURITY)
                    return BR_PREMATURE_COINBASE;
                if (got.value < 0 || got.value > BLK_MAX_MONEY)
                    return BR_INPUTVALUES_OUTOFRANGE;
                value_in += got.value;
                if (value_in > BLK_MAX_MONEY) return BR_INPUTVALUES_OUTOFRANGE;
                spk_bytes += got.spk.size();
            }
            // check_transaction holds every block of the production path
            // to the same ranges first; this entry stands alone behind the
            // C ABI, and an i64 sum must not overflow there either.
            i64 value_out;
            if (i32 r = sum_outputs(tx, value_out)) return r;
            if (value_in < value_out) return BR_IN_BELOWOUT;
            A.fees += value_in - value_out;
            if (A.fees < 0 || A.fees > BLK_MAX_MONEY) return BR_FEE_OUTOFRANGE;
        }
        sp.clear();
        for (const auto& s : spent) sp.push_back(&s);
        A.sigop_cost += tx_sigop_cost(tx, sp, flags);
        if (A.sigop_cost > BLK_MAX_SIGOPS_COST) return BR_BLK_SIGOPS;
        n_in += spent.size();
        // This tx's outputs, for later txs of the same block: read in
        // place. An outpoint the block already spent stays spent.
        for (u32 n = 0; n < tx.vout.size(); n++) {
            blk.block_probes++;
            BlockCoins::Slot& own = coins.probe(blk.txids[t].data(), n);
            own.out = &tx.vout[n];
            own.coinbase = cb;
        }
    }

    i64 cb_out;
    if (i32 r = sum_outputs(*blk.vtx[0], cb_out)) return r;
    if (cb_out > A.fees + blk_subsidy(height)) return BR_CB_AMOUNT;

    A.tx_index.resize(n_in);
    A.n_in.resize(n_in);
    A.amounts.resize(n_in);
    A.spk_offs.resize(n_in + 1);
    A.spk_blob.resize(spk_bytes);
    A.spent_digests.resize(n_tx);  // value-initialized: coinbase rows zero
    if (with_keys) A.script_keys.resize(32 * n_in);
    return BR_OK;
}

inline void block_acct_fill(NBlock& blk, SpentOutputs& all, u32 flags,
                            const u8* salt, size_t salt_len) {
    BlockAcct& A = blk.acct;
    size_t j = 0;  // the input's row
    i64 at = 0;    // its scriptPubKey's offset in spk_blob
    for (size_t t = 0; t < blk.vtx.size(); t++) {
        NTx& tx = *blk.vtx[t];
        if (tx_is_coinbase(tx)) continue;
        std::vector<NTxOut>& spent = all[t];
        size_t first = j;
        // sigcache.py spent_digest: per output amt 8LE || len(spk) 4LE || spk.
        Sha256 h;
        for (size_t i = 0; i < spent.size(); i++, j++) {
            const Bytes& spk = spent[i].spk;
            A.tx_index[j] = (i32)t;
            A.n_in[j] = (i32)i;
            A.amounts[j] = spent[i].value;
            A.spk_offs[j] = at;
            if (!spk.empty())
                std::memcpy(A.spk_blob.data() + at, spk.data(), spk.size());
            at += (i64)spk.size();
            hash_i64(h, spent[i].value);
            hash_part(h, spk.data(), (u32)spk.size());
        }
        u8* digest = A.spent_digests[t].data();
        h.finalize(digest);
        if (!A.script_keys.empty()) {
            // sha256(salt || wtxid, n_in 4LE, flags 4LE, digest as parts):
            // one midstate a tx, past the salt and the wtxid.
            Sha256 head;
            head.write(salt, salt_len);
            hash_part(head, blk.wtxids[t].data(), 32);
            for (u32 i = 0; i < spent.size(); i++) {
                Sha256 k = head;
                hash_u32(k, 4);  // a part goes in as len 4LE || bytes
                hash_u32(k, i);
                hash_u32(k, 4);
                hash_u32(k, flags);
                hash_part(k, digest, 32);
                k.finalize(A.script_keys.data() + 32 * (first + i));
            }
        }
        tx.precomp = Precomp();
        tx.legacy.clear();
        tx.precomp.spent_outputs = std::move(spent);
        tx.precomp.spent_ready = true;
        precompute_hashes(tx);
    }
    A.spk_offs[j] = at;
}

// Both passes. `salt` (the script-execution cache's) NULL makes no key.
inline i32 block_accounting(NBlock& blk, const NView& view, i64 height,
                            u32 flags, const u8* salt, size_t salt_len) {
    blk.stages = {};
    i64 at = steady_ns();
    SpentOutputs spent;
    i32 r = block_acct_decide(blk, view, height, flags, salt != nullptr, spent);
    at = blk.stages.stamp(NBlock::ST_DECIDE, at);
    if (r != BR_OK) return r;
    block_acct_fill(blk, spent, flags, salt, salt_len);
    blk.acct.ready = true;
    blk.stages.stamp(NBlock::ST_FILL, at);
    return BR_OK;
}

// What a block's apply took out of the view, kept so that the apply can
// be taken back (undo.h CBlockUndo: the coins a block spent). `replaced`
// holds a coin that an output of the block overwrote: accounting's BIP30
// scan refuses such a block, but apply is reachable on its own through
// the C ABI and its inverse has to be exact there too. The `_end` columns
// cut both lists per transaction, so the undo replays the apply's steps
// backwards one transaction at a time.
struct NBlockUndo {
    struct Entry {
        NOutPoint key;
        NCoin coin;
    };
    std::vector<Entry> spent, replaced;
    std::vector<size_t> spent_end, replaced_end;
};

// UpdateCoins over the whole block (coins.cpp / validate.py phase 4).
// With `undo`, every coin the block removes or overwrites is moved into
// the record first.
inline void view_apply_block(NView& view, const NBlock& blk, i64 height,
                             NBlockUndo* undo = nullptr) {
    if (undo) *undo = NBlockUndo();
    for (size_t t = 0; t < blk.vtx.size(); t++) {
        const NTx& tx = *blk.vtx[t];
        bool cb = tx_is_coinbase(tx);
        blk.view_probes += (i64)tx.vout.size() + (cb ? 0 : (i64)tx.vin.size());
        if (!cb)
            for (const auto& in : tx.vin) {
                NOutPoint k = NView::key(in.prevout_hash, in.prevout_n);
                if (!undo) {
                    view.map.erase(k);
                    continue;
                }
                auto it = view.map.find(k);
                if (it == view.map.end()) continue;
                undo->spent.push_back({k, std::move(it->second)});
                view.map.erase(it);
            }
        for (u32 n = 0; n < tx.vout.size(); n++) {
            NCoin coin{tx.vout[n].value, tx.vout[n].spk, (i32)height, cb};
            NOutPoint k = NView::key(blk.txids[t].data(), n);
            if (!undo) {
                view.map.insert_or_assign(k, std::move(coin));
                continue;
            }
            auto at = view.map.try_emplace(k);
            if (!at.second)
                undo->replaced.push_back({k, std::move(at.first->second)});
            at.first->second = std::move(coin);
        }
        if (undo) {
            undo->spent_end.push_back(undo->spent.size());
            undo->replaced_end.push_back(undo->replaced.size());
        }
    }
}

// DisconnectBlock's view half (validation.cpp): the exact inverse of
// view_apply_block(view, blk, height, &undo), transactions last to first:
// a transaction's outputs go (or give way to the coin they overwrote),
// then the coins it spent come back, among them one that an earlier
// transaction of this block created and that transaction's own turn then
// removes. The record holds its coins by value and is left as it was: it
// puts back any view that is in the state the apply left, as often as
// asked. Returns false, with the view untouched, when the record was not
// made from a block of this many transactions.
inline bool view_undo_block(NView& view, const NBlock& blk,
                            const NBlockUndo& undo) {
    size_t n_tx = blk.vtx.size();
    if (undo.spent_end.size() != n_tx || undo.replaced_end.size() != n_tx)
        return false;
    for (size_t t = n_tx; t-- > 0;) {
        const NTx& tx = *blk.vtx[t];
        for (u32 n = 0; n < tx.vout.size(); n++)
            view.map.erase(NView::key(blk.txids[t].data(), n));
        size_t lo = t ? undo.replaced_end[t - 1] : 0;
        for (size_t i = undo.replaced_end[t]; i-- > lo;)
            view.map.insert_or_assign(undo.replaced[i].key,
                                      undo.replaced[i].coin);
        lo = t ? undo.spent_end[t - 1] : 0;
        for (size_t i = undo.spent_end[t]; i-- > lo;)
            view.map.insert_or_assign(undo.spent[i].key, undo.spent[i].coin);
    }
    return true;
}

// DisconnectBlock (validation.cpp) with its checks, for a caller who was
// handed a block's record (`apply` with `undo`) and now takes the block off
// the tip: the outcomes are Core's three.
enum DisconnectResult : i32 {
    DISCONNECT_OK = 0,
    DISCONNECT_UNCLEAN = 1,  // the view is not where this block left it
    DISCONNECT_FAILED = 2,   // the record is not this block's
};

// What a disconnect did, for the caller's counters: the view's probes (a
// find with its erase, or an insert, by outpoint), the coins put back and
// the outputs taken out.
struct DisconnectStats {
    i64 probes = 0, restored = 0, removed = 0;
};

// The record against the block, before the view is looked at (FAILED in
// Core wherever a count disagrees; here also where a coin of the record is
// not the one the block's input names: this record keeps each coin's
// outpoint, Core's takes it from the block, and a coin restored under
// another block's outpoint would pass as clean). A record that holds an
// overwritten coin was not made behind the BIP30 scan of a connect.
inline bool undo_matches_block(const NBlock& blk, const NBlockUndo& undo) {
    size_t n_tx = blk.vtx.size();
    if (undo.spent_end.size() != n_tx || undo.replaced_end.size() != n_tx ||
        !undo.replaced.empty())
        return false;
    size_t at = 0;
    for (size_t t = 0; t < n_tx; t++) {
        const NTx& tx = *blk.vtx[t];
        size_t want = tx_is_coinbase(tx) ? 0 : tx.vin.size();
        if (undo.spent_end[t] != at + want || undo.spent_end[t] > undo.spent.size())
            return false;
        for (size_t i = 0; i < want; i++, at++)
            if (!(undo.spent[at].key ==
                  NView::key(tx.vin[i].prevout_hash, tx.vin[i].prevout_n)))
                return false;
    }
    return at == undo.spent.size();
}

// The view half, transactions last to first as DisconnectBlock goes: each
// output of the transaction has to be in the view as the block made it
// (value, script, `height`, coinbase flag) and is taken out; each coin the
// transaction spent is put back, last input first, and nothing may stand
// where it goes (ApplyTxInUndo). Unlike Core the outputs it would call
// unspendable are checked and removed too: this view holds them. Core
// works on a cache that DisconnectTip flushes on DISCONNECT_OK alone; here
// the steps are made on the view and noted, and the first that is not
// clean takes all of them back, newest first, so that a caller sees a
// view written only by a clean disconnect. One probe a coin on that path.
// The record is read, never consumed, and is one undo_matches_block has
// passed for this block: its counts index the record unchecked here.
inline i32 view_disconnect_block(NView& view, const NBlock& blk,
                                 const NBlockUndo& undo, i64 height,
                                 DisconnectStats& st) {
    st = DisconnectStats();
    using Node = decltype(view.map)::node_type;
    std::vector<Node> taken;              // outputs removed, in order
    std::vector<const NOutPoint*> put;    // coins restored, in order
    std::vector<bool> was_put;            // the steps' kinds, in order
    auto take_back = [&] {
        size_t ti = taken.size(), pi = put.size();
        for (size_t s = was_put.size(); s-- > 0;) {
            if (was_put[s])
                view.map.erase(*put[--pi]);
            else
                view.map.insert(std::move(taken[--ti]));
        }
        st.restored = st.removed = 0;
        return DISCONNECT_UNCLEAN;
    };
    for (size_t t = blk.vtx.size(); t-- > 0;) {
        const NTx& tx = *blk.vtx[t];
        bool cb = tx_is_coinbase(tx);
        for (u32 n = 0; n < tx.vout.size(); n++) {
            st.probes++;
            auto it = view.map.find(NView::key(blk.txids[t].data(), n));
            if (it == view.map.end()) return take_back();
            const NCoin& c = it->second;
            if (c.value != tx.vout[n].value || c.spk != tx.vout[n].spk ||
                c.height != (i32)height || c.coinbase != cb)
                return take_back();
            taken.push_back(view.map.extract(it));
            was_put.push_back(false);
            st.removed++;
        }
        size_t lo = t ? undo.spent_end[t - 1] : 0;
        for (size_t i = undo.spent_end[t]; i-- > lo;) {
            st.probes++;
            auto at = view.map.try_emplace(undo.spent[i].key);
            if (!at.second) return take_back();
            at.first->second = undo.spent[i].coin;
            put.push_back(&undo.spent[i].key);
            was_put.push_back(true);
            st.restored++;
        }
    }
    return DISCONNECT_OK;
}

// Order-free digest of the whole view: the XOR of sha256(outpoint ||
// value || height || coinbase || scriptPubKey) over its coins. Two views
// hold the same coins exactly when their sizes and digests agree (a map
// has no duplicate key, so no pair cancels).
inline void coin_digest_xor(const NOutPoint& key, const NCoin& coin,
                            u8 acc[32]) {
    Sha256 h;
    h.write(key.b, sizeof key.b);
    u8 meta[13];
    u64 v = (u64)coin.value;
    for (int j = 0; j < 8; j++) meta[j] = u8(v >> (8 * j));
    u32 ht = (u32)coin.height;
    for (int j = 0; j < 4; j++) meta[8 + j] = u8(ht >> (8 * j));
    meta[12] = coin.coinbase ? 1 : 0;
    h.write(meta, 13);
    h.write(coin.spk.data(), coin.spk.size());
    u8 d[32];
    h.finalize(d);
    for (int j = 0; j < 32; j++) acc[j] ^= d[j];
}

// Over the coins of the map's buckets [lo, hi): the XOR of such parts over
// a cut of [0, bucket_count()) is the view's digest, so several threads
// can make a large view's (nat_view_digest).
inline void view_digest_buckets(const NView& view, size_t lo, size_t hi,
                                u8 out[32]) {
    std::memset(out, 0, 32);
    for (size_t b = lo; b < hi; b++)
        for (auto it = view.map.begin(b); it != view.map.end(b); ++it)
            coin_digest_xor(it->first, it->second, out);
}

}  // namespace nat

// Native host consensus core: transaction codec, signature hashes
// (legacy / BIP143 / BIP341) and the full script interpreter with the
// deferred-signature seam.
//
// This is the C++ twin of the Python engine in
// `bitcoinconsensus_tpu/core/{tx,serialize,script,sighash,interpreter}.py`
// — same rules, same ScriptError codes (core/script_error.py numbering),
// same deferral protocol (models/batch.py DeferringSignatureChecker).
// The Python engine remains the executable spec; tests/test_native_interp.py
// asserts byte-for-byte agreement across the consensus vectors and random
// scripts. Reference anchors for the rules themselves:
// script/interpreter.cpp:431-1259 (EvalScript), :1937-2056 (VerifyScript),
// :1273-1364/:1577-1642 (legacy sighash), :1581-1625 (BIP143),
// :1491-1574 (BIP341), primitives/transaction.h:187-253 (codec),
// script/script.h:218-391 (CScriptNum).
#pragma once

#include <atomic>
#include <chrono>
#include <cstdint>
#include <cstring>
#include <map>
#include <memory>
#include <mutex>
#include <random>
#include <set>
#include <stdexcept>
#include <string>
#include <vector>

#include "hash_extra.hpp"
#include "secp.hpp"
#include "sha256.hpp"

namespace nat {

using Bytes = std::vector<u8>;

// --------------------------------------------------------------------------
// Script error codes: EXACT mirror of core/script_error.py (IntEnum order).
enum ScriptErr : i32 {
    SE_OK = 0,
    SE_UNKNOWN_ERROR,
    SE_EVAL_FALSE,
    SE_OP_RETURN,
    SE_SCRIPT_SIZE,
    SE_PUSH_SIZE,
    SE_OP_COUNT,
    SE_STACK_SIZE,
    SE_SIG_COUNT,
    SE_PUBKEY_COUNT,
    SE_VERIFY,
    SE_EQUALVERIFY,
    SE_CHECKMULTISIGVERIFY,
    SE_CHECKSIGVERIFY,
    SE_NUMEQUALVERIFY,
    SE_BAD_OPCODE,
    SE_DISABLED_OPCODE,
    SE_INVALID_STACK_OPERATION,
    SE_INVALID_ALTSTACK_OPERATION,
    SE_UNBALANCED_CONDITIONAL,
    SE_NEGATIVE_LOCKTIME,
    SE_UNSATISFIED_LOCKTIME,
    SE_SIG_HASHTYPE,
    SE_SIG_DER,
    SE_MINIMALDATA,
    SE_SIG_PUSHONLY,
    SE_SIG_HIGH_S,
    SE_SIG_NULLDUMMY,
    SE_PUBKEYTYPE,
    SE_CLEANSTACK,
    SE_MINIMALIF,
    SE_SIG_NULLFAIL,
    SE_DISCOURAGE_UPGRADABLE_NOPS,
    SE_DISCOURAGE_UPGRADABLE_WITNESS_PROGRAM,
    SE_DISCOURAGE_UPGRADABLE_TAPROOT_VERSION,
    SE_DISCOURAGE_OP_SUCCESS,
    SE_DISCOURAGE_UPGRADABLE_PUBKEYTYPE,
    SE_WITNESS_PROGRAM_WRONG_LENGTH,
    SE_WITNESS_PROGRAM_WITNESS_EMPTY,
    SE_WITNESS_PROGRAM_MISMATCH,
    SE_WITNESS_MALLEATED,
    SE_WITNESS_MALLEATED_P2SH,
    SE_WITNESS_UNEXPECTED,
    SE_WITNESS_PUBKEYTYPE,
    SE_SCHNORR_SIG_SIZE,
    SE_SCHNORR_SIG_HASHTYPE,
    SE_SCHNORR_SIG,
    SE_TAPROOT_WRONG_CONTROL_SIZE,
    SE_TAPSCRIPT_VALIDATION_WEIGHT,
    SE_TAPSCRIPT_CHECKMULTISIG,
    SE_TAPSCRIPT_MINIMALIF,
    SE_OP_CODESEPARATOR,
    SE_SIG_FINDANDDELETE,
};

// Verification flag bits: mirror of core/flags.py / interpreter.h:41-142.
enum : u32 {
    F_P2SH = 1u << 0,
    F_STRICTENC = 1u << 1,
    F_DERSIG = 1u << 2,
    F_LOW_S = 1u << 3,
    F_NULLDUMMY = 1u << 4,
    F_SIGPUSHONLY = 1u << 5,
    F_MINIMALDATA = 1u << 6,
    F_DISCOURAGE_UPGRADABLE_NOPS = 1u << 7,
    F_CLEANSTACK = 1u << 8,
    F_CLTV = 1u << 9,
    F_CSV = 1u << 10,
    F_WITNESS = 1u << 11,
    F_DISCOURAGE_UPGRADABLE_WITNESS_PROGRAM = 1u << 12,
    F_MINIMALIF = 1u << 13,
    F_NULLFAIL = 1u << 14,
    F_WITNESS_PUBKEYTYPE = 1u << 15,
    F_CONST_SCRIPTCODE = 1u << 16,
    F_TAPROOT = 1u << 17,
    F_DISCOURAGE_UPGRADABLE_TAPROOT_VERSION = 1u << 18,
    F_DISCOURAGE_OP_SUCCESS = 1u << 19,
    F_DISCOURAGE_UPGRADABLE_PUBKEYTYPE = 1u << 20,
};

// Consensus limits (script.h:23-56).
constexpr size_t MAX_SCRIPT_ELEMENT_SIZE = 520;
constexpr int MAX_OPS_PER_SCRIPT = 201;
constexpr int MAX_PUBKEYS_PER_MULTISIG = 20;
constexpr size_t MAX_SCRIPT_SIZE = 10000;
constexpr size_t MAX_STACK_SIZE = 1000;
constexpr i64 LOCKTIME_THRESHOLD = 500000000;
constexpr u8 ANNEX_TAG = 0x50;
constexpr i64 VALIDATION_WEIGHT_PER_SIGOP_PASSED = 50;
constexpr i64 VALIDATION_WEIGHT_OFFSET = 50;
constexpr u64 SER_MAX_SIZE = 0x02000000;  // serialize.h MAX_SIZE

// Opcodes used by name below.
enum : int {
    OP_0 = 0x00, OP_PUSHDATA1 = 0x4C, OP_PUSHDATA2 = 0x4D, OP_PUSHDATA4 = 0x4E,
    OP_1NEGATE = 0x4F, OP_RESERVED = 0x50, OP_1 = 0x51, OP_16 = 0x60,
    OP_NOP = 0x61, OP_VER = 0x62, OP_IF = 0x63, OP_NOTIF = 0x64,
    OP_VERIF = 0x65, OP_VERNOTIF = 0x66, OP_ELSE = 0x67, OP_ENDIF = 0x68,
    OP_VERIFY = 0x69, OP_RETURN = 0x6A, OP_TOALTSTACK = 0x6B,
    OP_FROMALTSTACK = 0x6C, OP_2DROP = 0x6D, OP_2DUP = 0x6E, OP_3DUP = 0x6F,
    OP_2OVER = 0x70, OP_2ROT = 0x71, OP_2SWAP = 0x72, OP_IFDUP = 0x73,
    OP_DEPTH = 0x74, OP_DROP = 0x75, OP_DUP = 0x76, OP_NIP = 0x77,
    OP_OVER = 0x78, OP_PICK = 0x79, OP_ROLL = 0x7A, OP_ROT = 0x7B,
    OP_SWAP = 0x7C, OP_TUCK = 0x7D, OP_CAT = 0x7E, OP_SUBSTR = 0x7F,
    OP_LEFT = 0x80, OP_RIGHT = 0x81, OP_SIZE = 0x82, OP_INVERT = 0x83,
    OP_AND = 0x84, OP_OR = 0x85, OP_XOR = 0x86, OP_EQUAL = 0x87,
    OP_EQUALVERIFY = 0x88, OP_RESERVED1 = 0x89, OP_RESERVED2 = 0x8A,
    OP_1ADD = 0x8B, OP_1SUB = 0x8C, OP_2MUL = 0x8D, OP_2DIV = 0x8E,
    OP_NEGATE = 0x8F, OP_ABS = 0x90, OP_NOT = 0x91, OP_0NOTEQUAL = 0x92,
    OP_ADD = 0x93, OP_SUB = 0x94, OP_MUL = 0x95, OP_DIV = 0x96,
    OP_MOD = 0x97, OP_LSHIFT = 0x98, OP_RSHIFT = 0x99, OP_BOOLAND = 0x9A,
    OP_BOOLOR = 0x9B, OP_NUMEQUAL = 0x9C, OP_NUMEQUALVERIFY = 0x9D,
    OP_NUMNOTEQUAL = 0x9E, OP_LESSTHAN = 0x9F, OP_GREATERTHAN = 0xA0,
    OP_LESSTHANOREQUAL = 0xA1, OP_GREATERTHANOREQUAL = 0xA2, OP_MIN = 0xA3,
    OP_MAX = 0xA4, OP_WITHIN = 0xA5, OP_RIPEMD160 = 0xA6, OP_SHA1 = 0xA7,
    OP_SHA256 = 0xA8, OP_HASH160 = 0xA9, OP_HASH256 = 0xAA,
    OP_CODESEPARATOR = 0xAB, OP_CHECKSIG = 0xAC, OP_CHECKSIGVERIFY = 0xAD,
    OP_CHECKMULTISIG = 0xAE, OP_CHECKMULTISIGVERIFY = 0xAF, OP_NOP1 = 0xB0,
    OP_CLTV = 0xB1, OP_CSV = 0xB2, OP_NOP4 = 0xB3, OP_NOP10 = 0xB9,
    OP_CHECKSIGADD = 0xBA,
};

// SigVersion (interpreter.h).
enum : int { SV_BASE = 0, SV_WITNESS_V0 = 1, SV_TAPROOT = 2, SV_TAPSCRIPT = 3 };

// Sighash types.
enum : int {
    SH_DEFAULT = 0, SH_ALL = 1, SH_NONE = 2, SH_SINGLE = 3,
    SH_ANYONECANPAY = 0x80, SH_OUTPUT_MASK = 3, SH_INPUT_MASK = 0x80,
};

constexpr u32 SEQUENCE_FINAL = 0xFFFFFFFFu;
constexpr u32 SEQ_DISABLE = 1u << 31;
constexpr u32 SEQ_TYPE = 1u << 22;
constexpr u32 SEQ_MASK = 0x0000FFFFu;

// Taproot control-block geometry (interpreter.h:214-219).
constexpr u8 TAPROOT_LEAF_MASK = 0xFE;
constexpr u8 TAPROOT_LEAF_TAPSCRIPT = 0xC0;
constexpr size_t TAPROOT_CONTROL_BASE_SIZE = 33;
constexpr size_t TAPROOT_CONTROL_NODE_SIZE = 32;
constexpr size_t TAPROOT_CONTROL_MAX_NODE_COUNT = 128;
constexpr size_t TAPROOT_CONTROL_MAX_SIZE =
    TAPROOT_CONTROL_BASE_SIZE + TAPROOT_CONTROL_NODE_SIZE * TAPROOT_CONTROL_MAX_NODE_COUNT;

// --------------------------------------------------------------------------
// Serialization

struct SerErr : std::runtime_error {
    using std::runtime_error::runtime_error;
};

struct Reader {
    const u8* data;
    size_t len;
    size_t pos = 0;

    Reader(const u8* d, size_t l) : data(d), len(l) {}

    const u8* read(size_t n) {
        if (pos + n > len) throw SerErr("read past end of data");
        const u8* p = data + pos;
        pos += n;
        return p;
    }
    u8 read_u8() { return *read(1); }
    u32 read_u32() {
        const u8* p = read(4);
        return (u32)p[0] | ((u32)p[1] << 8) | ((u32)p[2] << 16) | ((u32)p[3] << 24);
    }
    i32 read_i32() { return (i32)read_u32(); }
    u64 read_u64() {
        const u8* p = read(8);
        u64 v = 0;
        for (int i = 0; i < 8; i++) v |= (u64)p[i] << (8 * i);
        return v;
    }
    i64 read_i64() { return (i64)read_u64(); }
    u64 read_compact_size(bool range_check = true) {
        u8 first = read_u8();
        u64 size;
        if (first < 253) {
            size = first;
        } else if (first == 253) {
            const u8* p = read(2);
            size = (u64)p[0] | ((u64)p[1] << 8);
            if (size < 253) throw SerErr("non-canonical CompactSize");
        } else if (first == 254) {
            size = read_u32();
            if (size < 0x10000) throw SerErr("non-canonical CompactSize");
        } else {
            size = read_u64();
            if (size < 0x100000000ull) throw SerErr("non-canonical CompactSize");
        }
        if (range_check && size > SER_MAX_SIZE) throw SerErr("CompactSize exceeds MAX_SIZE");
        return size;
    }
    Bytes read_string() {
        u64 n = read_compact_size();
        const u8* p = read((size_t)n);
        return Bytes(p, p + n);
    }
};

inline void put_u32(Bytes& b, u32 v) {
    for (int i = 0; i < 4; i++) b.push_back(u8(v >> (8 * i)));
}
inline void put_i64(Bytes& b, i64 v) {
    u64 u = (u64)v;
    for (int i = 0; i < 8; i++) b.push_back(u8(u >> (8 * i)));
}
inline void put_compact_size(Bytes& b, u64 n) {
    if (n < 253) {
        b.push_back(u8(n));
    } else if (n <= 0xFFFF) {
        b.push_back(0xFD);
        b.push_back(u8(n));
        b.push_back(u8(n >> 8));
    } else if (n <= 0xFFFFFFFFull) {
        b.push_back(0xFE);
        put_u32(b, (u32)n);
    } else {
        b.push_back(0xFF);
        put_i64(b, (i64)n);
    }
}
inline void put_bytes(Bytes& b, const Bytes& s) {
    b.insert(b.end(), s.begin(), s.end());
}
inline void put_string(Bytes& b, const Bytes& s) {
    put_compact_size(b, s.size());
    put_bytes(b, s);
}

// The same encodings written straight into a hash.
inline void hash_u32(Sha256& h, u32 v) {
    u8 b[4] = {u8(v), u8(v >> 8), u8(v >> 16), u8(v >> 24)};
    h.write(b, 4);
}
inline void hash_i64(Sha256& h, i64 v) {
    u8 b[8];
    for (int i = 0; i < 8; i++) b[i] = u8((u64)v >> (8 * i));
    h.write(b, 8);
}
// A cache key's part (models/sigcache.py `_key`): len 4LE || bytes.
inline void hash_part(Sha256& h, const u8* p, size_t len) {
    hash_u32(h, (u32)len);
    h.write(p, len);
}
inline void hash_compact_size(Sha256& h, u64 n) {
    u8 b[9];
    size_t k = n < 253 ? 0 : n <= 0xFFFF ? 2 : n <= 0xFFFFFFFFull ? 4 : 8;
    b[0] = k == 0 ? u8(n) : k == 2 ? 0xFD : k == 4 ? 0xFE : 0xFF;
    for (size_t i = 0; i < k; i++) b[1 + i] = u8(n >> (8 * i));
    h.write(b, 1 + k);
}

// --------------------------------------------------------------------------
// Transaction

struct NTxIn {
    u8 prevout_hash[32];
    u32 prevout_n;
    Bytes script_sig;
    u32 sequence;
    std::vector<Bytes> witness;
};

struct NTxOut {
    i64 value;
    Bytes spk;

    Bytes serialize() const {
        Bytes b;
        put_i64(b, value);
        put_string(b, spk);
        return b;
    }
};

inline void hash_txout(Sha256& h, const NTxOut& out) {
    hash_i64(h, out.value);
    hash_compact_size(h, out.spk.size());
    h.write(out.spk.data(), out.spk.size());
}

struct Precomp {
    bool ready = false;
    bool spent_ready = false;
    bool bip143_ready = false;
    bool bip341_ready = false;
    u8 prevouts_single[32], sequences_single[32], outputs_single[32];
    u8 spent_amounts_single[32], spent_scripts_single[32];
    u8 hash_prevouts[32], hash_sequence[32], hash_outputs[32];
    std::vector<NTxOut> spent_outputs;
    u8 spent_digest[32] = {0};  // cache key over the registered prevouts
};

// The part of a legacy preimage (CTransactionSignatureSerializer's output)
// that does not depend on which input signs, laid down once a transaction,
// on the first legacy digest anyone asks of it, and hashed as spans by
// `legacy_sighash`. `all`: for every input prevout (36) || 0x00 || sequence
// (4), a stride of STRIDE bytes, then compact_size(n_out) || outputs.
// `zero_seq`, what SIGHASH_NONE and SIGHASH_SINGLE sign: the same inputs
// string with every sequence zero, and no outputs.
// Beside each string lie the SHA-256 states of the stream every such digest
// starts with, version || compact_size(n_in) || string, at each multiple of
// GRID bytes up to the longest prefix a digest shares with it (the last
// input's, which ends where its script begins): input i's preimage equals
// input i-1's up to input i-1's script, so a digest resumes from the last
// grid point at or before its own script and hashes from there. A stream
// that never reaches GRID bytes (under ~100 inputs) has no grid point, pays
// nothing for the table and is hashed whole. GRID is a multiple of the
// 64-byte block; 4,096 keeps the table at 32 bytes a hundred inputs and
// the bytes hashed again under GRID a digest (1.8 % of a digest's mean at
// 5,569 inputs).
// The interpreter's workers share an NTx (inputs of one transaction are
// drawn by several), so a first asker builds string and table under `mu`
// while the others wait for it; a reader after `ready` takes no lock. Use
// never changes either.
struct NTx;
struct LegacyTemplate {
    static constexpr size_t STRIDE = 41, SCRIPT_AT = 36, GRID = 4096;
    enum : int { EV_BUILT = 0, EV_SERVED, EV_RESUMED, EV_COUNT };  // Session's counts
    struct Blank {
        std::atomic<bool> ready{false};
        Bytes bytes;
        size_t header = 0;        // version || compact_size(n_in): the stream's first bytes
        std::vector<u32> states;  // eight words a grid point: after GRID, 2 GRID, ... bytes
    };
    std::mutex mu;
    Blank all, zero_seq;

    // The string a digest hashes from and its grid, built if this is its
    // first asker; `events`, where given, counts the build and the serving.
    inline const Blank& get(const NTx& tx, bool zeroed, i64* events);

    void clear() {
        for (Blank* b : {&all, &zero_seq}) {
            b->ready.store(false, std::memory_order_relaxed);
            b->bytes.clear();
            b->states.clear();
        }
    }
};

struct NTx {
    i32 version;
    std::vector<NTxIn> vin;
    std::vector<NTxOut> vout;
    u32 locktime;
    // The wire bytes this tx was read from, as offsets into the reader's
    // data: [span_lo, span_lo + ser_size) is the whole tx and, being what
    // a canonical reader consumed, equals serialize(true); [body_lo,
    // body_hi) runs from the vin count to the last output, so version ||
    // body || locktime equals serialize(false).
    size_t span_lo, body_lo, body_hi;
    i64 ser_size;  // serialized size incl. witness (for the size check)
    Precomp precomp;
    mutable LegacyTemplate legacy;  // cleared wherever `precomp` is

    bool has_witness() const {
        for (const auto& in : vin)
            if (!in.witness.empty()) return true;
        return false;
    }

    Bytes serialize(bool include_witness) const {
        bool use_wit = include_witness && has_witness();
        Bytes b;
        put_u32(b, (u32)version);
        if (use_wit) {
            b.push_back(0);
            b.push_back(1);
        }
        put_compact_size(b, vin.size());
        for (const auto& in : vin) {
            b.insert(b.end(), in.prevout_hash, in.prevout_hash + 32);
            put_u32(b, in.prevout_n);
            put_string(b, in.script_sig);
            put_u32(b, in.sequence);
        }
        put_compact_size(b, vout.size());
        for (const auto& out : vout) {
            put_i64(b, out.value);
            put_string(b, out.spk);
        }
        if (use_wit) {
            for (const auto& in : vin) {
                put_compact_size(b, in.witness.size());
                for (const auto& w : in.witness) put_string(b, w);
            }
        }
        put_u32(b, locktime);
        return b;
    }
};

inline const LegacyTemplate::Blank& LegacyTemplate::get(const NTx& tx, bool zeroed,
                                                        i64* events) {
    Blank& b = zeroed ? zero_seq : all;
    if (events) events[EV_SERVED]++;
    if (b.ready.load(std::memory_order_acquire)) return b;
    std::lock_guard<std::mutex> lock(mu);
    if (b.ready.load(std::memory_order_relaxed)) return b;
    Bytes& s = b.bytes;
    size_t outputs = 9;  // an upper bound: a compact size is nine bytes at most
    if (!zeroed)
        for (const NTxOut& out : tx.vout) outputs += 8 + 9 + out.spk.size();
    s.reserve(STRIDE * tx.vin.size() + (zeroed ? 0 : outputs));
    for (const NTxIn& in : tx.vin) {
        s.insert(s.end(), in.prevout_hash, in.prevout_hash + 32);
        put_u32(s, in.prevout_n);
        s.push_back(0);  // the blanked script: an empty string
        put_u32(s, zeroed ? 0 : in.sequence);
    }
    if (!zeroed) {
        put_compact_size(s, tx.vout.size());
        for (const NTxOut& out : tx.vout) {
            put_i64(s, out.value);
            put_string(s, out.spk);
        }
    }
    // The grid: one pass over the stream as far as the last input's script.
    Sha256 h;
    hash_u32(h, (u32)tx.version);
    hash_compact_size(h, tx.vin.size());
    b.header = (size_t)h.bytes;
    size_t longest = tx.vin.empty() ? 0 : b.header + STRIDE * (tx.vin.size() - 1) + SCRIPT_AT;
    b.states.reserve(8 * (longest / GRID));
    for (size_t point = GRID; point <= longest; point += GRID) {
        h.write(s.data() + ((size_t)h.bytes - b.header), point - (size_t)h.bytes);
        b.states.insert(b.states.end(), h.s, h.s + 8);  // whole blocks absorbed: `s` is the midstate
    }
    if (events) events[EV_BUILT]++;
    b.ready.store(true, std::memory_order_release);
    return b;
}

// Exact mirror of UnserializeTransaction (transaction.h:187-224 /
// core/tx.py _deserialize_from). Throws SerErr. Vectors grow
// INCREMENTALLY (one entry per parsed element, each consuming >= 1 input
// byte) — never pre-sized from the attacker-claimed CompactSize, so a
// tiny malformed tx cannot demand a multi-GB allocation.
inline NTx* tx_parse_from(Reader& r) {
    auto tx = std::make_unique<NTx>();
    tx->span_lo = r.pos;
    tx->version = r.read_i32();
    u8 flags = 0;
    tx->body_lo = r.pos;
    u64 n_vin = r.read_compact_size();
    auto read_txin = [&](NTxIn& in) {
        const u8* h = r.read(32);
        std::memcpy(in.prevout_hash, h, 32);
        in.prevout_n = r.read_u32();
        in.script_sig = r.read_string();
        in.sequence = r.read_u32();
    };
    auto read_vin = [&](u64 n) {
        for (u64 i = 0; i < n; i++) {
            tx->vin.emplace_back();
            read_txin(tx->vin.back());
        }
    };
    auto read_vout = [&](u64 n) {
        for (u64 i = 0; i < n; i++) {
            tx->vout.emplace_back();
            tx->vout.back().value = r.read_i64();
            tx->vout.back().spk = r.read_string();
        }
    };
    read_vin(n_vin);
    if (tx->vin.empty()) {
        flags = r.read_u8();
        if (flags != 0) {
            tx->body_lo = r.pos;  // past the marker and the flag byte
            read_vin(r.read_compact_size());
            read_vout(r.read_compact_size());
        }
    } else {
        read_vout(r.read_compact_size());
    }
    tx->body_hi = r.pos;
    if (flags & 1) {
        flags ^= 1;
        bool any = false;
        for (auto& in : tx->vin) {
            u64 n = r.read_compact_size();
            for (u64 i = 0; i < n; i++) in.witness.push_back(r.read_string());
            if (n) any = true;
        }
        if (!any) throw SerErr("Superfluous witness record");
    }
    if (flags) throw SerErr("Unknown transaction optional data");
    tx->locktime = r.read_u32();
    // The reader refuses a non-canonical CompactSize and a witness record
    // with no witness in it, so what it consumed is serialize(true).
    tx->ser_size = (i64)(r.pos - tx->span_lo);
    return tx.release();
}

inline NTx* tx_parse(const u8* data, size_t len) {
    Reader r(data, len);
    return tx_parse_from(r);
}

// --------------------------------------------------------------------------
// Script decoding / predicates (core/script.py twins)

struct Span {
    const u8* p;
    size_t n;
    u8 operator[](size_t i) const { return p[i]; }
    size_t size() const { return n; }
    Span sub(size_t off) const { return {p + off, n - off}; }
    Span sub(size_t off, size_t cnt) const { return {p + off, cnt}; }
};

inline Span span_of(const Bytes& b) { return {b.data(), b.size()}; }

// Decode one op; returns false on truncated push (opcode -> -1).
inline bool decode_op(Span s, size_t& pos, int& opcode, const u8** data,
                      size_t* dlen) {
    opcode = s[pos];
    pos += 1;
    *data = nullptr;
    *dlen = 0;
    if (opcode > OP_PUSHDATA4) return true;
    u64 size;
    if (opcode < OP_PUSHDATA1) {
        size = (u64)opcode;
    } else if (opcode == OP_PUSHDATA1) {
        if (pos + 1 > s.size()) return false;
        size = s[pos];
        pos += 1;
    } else if (opcode == OP_PUSHDATA2) {
        if (pos + 2 > s.size()) return false;
        size = (u64)s[pos] | ((u64)s[pos + 1] << 8);
        pos += 2;
    } else {
        if (pos + 4 > s.size()) return false;
        size = (u64)s[pos] | ((u64)s[pos + 1] << 8) | ((u64)s[pos + 2] << 16) |
               ((u64)s[pos + 3] << 24);
        pos += 4;
    }
    if (pos + size > s.size()) return false;
    *data = s.p + pos;
    *dlen = (size_t)size;
    pos += (size_t)size;
    return true;
}

inline Bytes push_data_enc(const Bytes& d) {
    Bytes out;
    size_t n = d.size();
    if (n < OP_PUSHDATA1) {
        out.push_back(u8(n));
    } else if (n <= 0xFF) {
        out.push_back(OP_PUSHDATA1);
        out.push_back(u8(n));
    } else if (n <= 0xFFFF) {
        out.push_back(OP_PUSHDATA2);
        out.push_back(u8(n));
        out.push_back(u8(n >> 8));
    } else {
        out.push_back(OP_PUSHDATA4);
        put_u32(out, (u32)n);
    }
    put_bytes(out, d);
    return out;
}

inline bool check_minimal_push(const u8* d, size_t n, int opcode) {
    if (n == 0) return opcode == OP_0;
    if (n == 1 && d[0] >= 1 && d[0] <= 16) return false;
    if (n == 1 && d[0] == 0x81) return false;
    if (n <= 75) return opcode == (int)n;
    if (n <= 255) return opcode == OP_PUSHDATA1;
    if (n <= 65535) return opcode == OP_PUSHDATA2;
    return true;
}

inline bool is_p2sh(const Bytes& s) {
    return s.size() == 23 && s[0] == OP_HASH160 && s[1] == 0x14 && s[22] == OP_EQUAL;
}

inline bool is_witness_program(const Bytes& s, int* version, Bytes* program) {
    if (s.size() < 4 || s.size() > 42) return false;
    if (s[0] != OP_0 && !(s[0] >= OP_1 && s[0] <= OP_16)) return false;
    if ((size_t)s[1] + 2 != s.size()) return false;
    *version = s[0] == OP_0 ? 0 : s[0] - OP_1 + 1;
    program->assign(s.begin() + 2, s.end());
    return true;
}

inline bool is_push_only(const Bytes& s) {
    Span sp = span_of(s);
    size_t pos = 0;
    while (pos < sp.size()) {
        int opcode;
        const u8* d;
        size_t dl;
        if (!decode_op(sp, pos, opcode, &d, &dl)) return false;
        if (opcode > OP_16) return false;
    }
    return true;
}

inline bool is_op_success(int op) {
    return op == 0x50 || op == 0x62 || (0x7E <= op && op <= 0x81) ||
           (0x83 <= op && op <= 0x86) || (0x89 <= op && op <= 0x8A) ||
           (0x8D <= op && op <= 0x8E) || (0x95 <= op && op <= 0x99) ||
           (0xBB <= op && op <= 0xFE);
}

// FindAndDelete (core/script.py find_and_delete semantics).
inline int find_and_delete(Bytes& script, const Bytes& needle) {
    if (needle.empty()) return 0;
    Bytes out;
    int n_found = 0;
    Span sp = span_of(script);
    size_t pos = 0, last = 0;
    while (pos < sp.size()) {
        out.insert(out.end(), sp.p + last, sp.p + pos);
        while (pos + needle.size() <= sp.size() &&
               std::memcmp(sp.p + pos, needle.data(), needle.size()) == 0) {
            pos += needle.size();
            n_found++;
        }
        last = pos;
        if (pos < sp.size()) {
            int opcode;
            const u8* d;
            size_t dl;
            if (!decode_op(sp, pos, opcode, &d, &dl)) break;
        } else {
            break;
        }
    }
    out.insert(out.end(), sp.p + last, sp.p + sp.size());
    if (n_found) script = out;
    return n_found;
}

// --------------------------------------------------------------------------
// CScriptNum

struct ScriptNumErr : std::runtime_error {
    using std::runtime_error::runtime_error;
};

inline i64 script_num_decode(const Bytes& d, bool require_minimal,
                             size_t max_size = 4) {
    if (d.size() > max_size) throw ScriptNumErr("script number overflow");
    if (require_minimal && !d.empty()) {
        if ((d.back() & 0x7F) == 0) {
            if (d.size() <= 1 || !(d[d.size() - 2] & 0x80))
                throw ScriptNumErr("non-minimally encoded script number");
        }
    }
    if (d.empty()) return 0;
    u64 result = 0;
    for (size_t i = 0; i < d.size(); i++) result |= (u64)d[i] << (8 * i);
    if (d.back() & 0x80) {
        result &= ~((u64)0x80 << (8 * (d.size() - 1)));
        return -(i64)result;
    }
    return (i64)result;
}

inline Bytes script_num_encode(i64 n) {
    Bytes out;
    if (n == 0) return out;
    bool negative = n < 0;
    u64 absvalue = negative ? (u64)(-(n + 1)) + 1 : (u64)n;
    while (absvalue) {
        out.push_back(u8(absvalue & 0xFF));
        absvalue >>= 8;
    }
    if (out.back() & 0x80) {
        out.push_back(negative ? 0x80 : 0x00);
    } else if (negative) {
        out.back() |= 0x80;
    }
    return out;
}

inline bool script_num_to_bool(const Bytes& d) {
    for (size_t i = 0; i < d.size(); i++) {
        if (d[i] != 0) return !(i == d.size() - 1 && d[i] == 0x80);
    }
    return false;
}

inline i64 clamp_int(i64 v) {
    if (v > 0x7FFFFFFFll) return 0x7FFFFFFFll;
    if (v < -0x80000000ll) return -0x80000000ll;
    return v;
}

// --------------------------------------------------------------------------
// Sighash

inline const TagMidstate& TAG_TAPSIGHASH() {
    static TagMidstate t("TapSighash");
    return t;
}
inline const TagMidstate& TAG_TAPLEAF() {
    static TagMidstate t("TapLeaf");
    return t;
}
inline const TagMidstate& TAG_TAPBRANCH() {
    static TagMidstate t("TapBranch");
    return t;
}
inline const TagMidstate& TAG_TAPTWEAK() {
    static TagMidstate t("TapTweak");
    return t;
}

// SerializeScriptCode (core/sighash.py _serialize_script_code semantics),
// written into the hash.
inline void hash_script_code(Sha256& h, const Bytes& sc) {
    Span sp = span_of(sc);
    size_t n_codeseps = 0;
    size_t pos = 0;
    while (pos < sp.size()) {
        int opcode;
        const u8* d;
        size_t dl;
        if (!decode_op(sp, pos, opcode, &d, &dl)) break;
        if (opcode == OP_CODESEPARATOR) n_codeseps++;
    }
    hash_compact_size(h, sc.size() - n_codeseps);
    size_t seg_start = 0;
    pos = 0;
    while (pos < sp.size()) {
        int opcode;
        const u8* d;
        size_t dl;
        if (!decode_op(sp, pos, opcode, &d, &dl)) {
            // truncated push: decoder consumed opcode/length bytes only;
            // write the segment up to that point, drop the tail.
            h.write(sp.p + seg_start, pos - seg_start);
            return;
        }
        if (opcode == OP_CODESEPARATOR) {
            h.write(sp.p + seg_start, pos - 1 - seg_start);
            seg_start = pos;
        }
    }
    h.write(sp.p + seg_start, sp.size() - seg_start);
}

// `n` outputs as SIGHASH_SINGLE blanks them: value -1, an empty script.
inline void hash_blank_outputs(Sha256& h, size_t n) {
    constexpr size_t EACH = 9, CHUNK = 64;  // 576 bytes: nine whole blocks
    u8 blank[EACH * CHUNK];
    std::memset(blank, 0xFF, sizeof blank);
    for (size_t k = 0; k < CHUNK; k++) blank[EACH * k + 8] = 0;
    for (; n >= CHUNK; n -= CHUNK) h.write(blank, sizeof blank);
    h.write(blank, EACH * n);
}

// Both digests return the bytes they fed to the transform (0 for the
// SIGHASH_SINGLE "one" digest, which hashes nothing). The legacy one
// builds no preimage. Without ANYONECANPAY it hashes from the transaction's
// blanked template (LegacyTemplate; `events` counts its use): the stream up
// to this input's script, resumed from the template's last grid point at or
// before it where there is one (what that state had absorbed is not fed
// again and not counted), then the script code, then the span after it. With
// ANYONECANPAY there is one input, and its fields go in one by one.
inline size_t legacy_sighash(const Bytes& script_code, const NTx& tx, size_t n_in,
                             int hash_type, u8 out[32], i64* events = nullptr) {
    bool anyone = (hash_type & SH_ANYONECANPAY) != 0;
    int base = hash_type & 0x1F;
    bool hash_single = base == SH_SINGLE;
    bool hash_none = base == SH_NONE;
    if (hash_single && n_in >= tx.vout.size()) {
        std::memset(out, 0, 32);
        out[0] = 1;
        return 0;
    }
    const NTxIn& own = tx.vin[n_in];
    Sha256 h;
    size_t resumed = 0;  // bytes the grid point had absorbed
    if (anyone) {
        hash_u32(h, (u32)tx.version);
        hash_compact_size(h, 1);
        h.write(own.prevout_hash, 32);
        hash_u32(h, own.prevout_n);
        hash_script_code(h, script_code);
        hash_u32(h, own.sequence);
    } else {
        constexpr size_t STRIDE = LegacyTemplate::STRIDE, GRID = LegacyTemplate::GRID;
        bool zeroed = hash_single || hash_none;  // the others' sequences
        const LegacyTemplate::Blank& blank = tx.legacy.get(tx, zeroed, events);
        const Bytes& t = blank.bytes;
        size_t at = STRIDE * n_in + LegacyTemplate::SCRIPT_AT;
        size_t points = (blank.header + at) / GRID;
        if (points) {
            resumed = points * GRID;
            h.resume(&blank.states[8 * (points - 1)], resumed);
            if (events) events[LegacyTemplate::EV_RESUMED]++;
        } else {
            hash_u32(h, (u32)tx.version);
            hash_compact_size(h, tx.vin.size());
        }
        size_t from = (size_t)h.bytes - blank.header;
        h.write(t.data() + from, at - from);
        hash_script_code(h, script_code);
        if (zeroed) {
            hash_u32(h, own.sequence);
            at += 4;
        }
        h.write(t.data() + at + 1, t.size() - at - 1);  // SIGHASH_ALL: the outputs too
    }
    if (hash_none) {
        hash_compact_size(h, 0);
    } else if (hash_single) {
        hash_compact_size(h, n_in + 1);
        hash_blank_outputs(h, n_in);
        hash_txout(h, tx.vout[n_in]);
    } else if (anyone) {
        hash_compact_size(h, tx.vout.size());
        for (const NTxOut& o : tx.vout) hash_txout(h, o);
    }
    hash_u32(h, tx.locktime);
    hash_u32(h, (u32)(i32)hash_type);
    size_t hashed = (size_t)h.bytes - resumed;
    u8 once[32];
    h.finalize(once);
    sha256(once, 32, out);
    return hashed;
}

// The tx-wide single-SHA aggregates + BIP143 doubles of a Precomp whose
// spent outputs, if any, are already in place (spent_ready); spent
// aggregates when they are (interpreter.cpp:1422-1472). Streams every
// field into its hash: no buffer, no allocation, cannot throw.
inline void precompute_hashes(NTx& tx) {
    Precomp& pc = tx.precomp;
    pc.ready = true;
    bool uses143 = false, uses341 = false;
    for (size_t i = 0; i < tx.vin.size(); i++) {
        if (uses143 && uses341) break;
        if (!tx.vin[i].witness.empty()) {
            const Bytes* spk =
                pc.spent_ready ? &pc.spent_outputs[i].spk : nullptr;
            if (spk && spk->size() == 34 && (*spk)[0] == OP_1) uses341 = true;
            else uses143 = true;
        }
    }
    if (uses143 || uses341) {
        Sha256 prevouts, sequences, outputs;
        for (const auto& in : tx.vin) {
            prevouts.write(in.prevout_hash, 32);
            hash_u32(prevouts, in.prevout_n);
            hash_u32(sequences, in.sequence);
        }
        for (const auto& out : tx.vout) hash_txout(outputs, out);
        prevouts.finalize(pc.prevouts_single);
        sequences.finalize(pc.sequences_single);
        outputs.finalize(pc.outputs_single);
    }
    if (uses143) {
        sha256(pc.prevouts_single, 32, pc.hash_prevouts);
        sha256(pc.sequences_single, 32, pc.hash_sequence);
        sha256(pc.outputs_single, 32, pc.hash_outputs);
        pc.bip143_ready = true;
    }
    if (uses341 && pc.spent_ready) {
        Sha256 amounts, scripts;
        for (const auto& out : pc.spent_outputs) {
            hash_i64(amounts, out.value);
            hash_compact_size(scripts, out.spk.size());
            scripts.write(out.spk.data(), out.spk.size());
        }
        amounts.finalize(pc.spent_amounts_single);
        scripts.finalize(pc.spent_scripts_single);
        pc.bip341_ready = true;
    }
}

// Precompute from scratch, registering a copy of `spent` when given.
inline void precompute(NTx& tx, const std::vector<NTxOut>* spent) {
    Precomp& pc = tx.precomp;
    pc = Precomp();
    tx.legacy.clear();
    // A prevout list is only usable when it has exactly one entry per
    // input (interpreter.cpp:1512 readiness contract); a wrong-length
    // list is ignored rather than indexed out of bounds.
    if (spent && spent->size() == tx.vin.size()) {
        pc.spent_outputs = *spent;
        pc.spent_ready = true;
    }
    precompute_hashes(tx);
}

inline size_t bip143_sighash(const Bytes& script_code, const NTx& tx, size_t n_in,
                             int hash_type, i64 amount, u8 out[32]) {
    const Precomp& pc = tx.precomp;
    bool cacheready = pc.ready && pc.bip143_ready;
    u8 hash_prevouts[32] = {0}, hash_sequence[32] = {0}, hash_outputs[32] = {0};
    int base = hash_type & 0x1F;
    if (!(hash_type & SH_ANYONECANPAY)) {
        if (cacheready) {
            std::memcpy(hash_prevouts, pc.hash_prevouts, 32);
        } else {
            Bytes b;
            for (const auto& in : tx.vin) {
                b.insert(b.end(), in.prevout_hash, in.prevout_hash + 32);
                put_u32(b, in.prevout_n);
            }
            sha256d(b.data(), b.size(), hash_prevouts);
        }
    }
    if (!(hash_type & SH_ANYONECANPAY) && base != SH_SINGLE && base != SH_NONE) {
        if (cacheready) {
            std::memcpy(hash_sequence, pc.hash_sequence, 32);
        } else {
            Bytes b;
            for (const auto& in : tx.vin) put_u32(b, in.sequence);
            sha256d(b.data(), b.size(), hash_sequence);
        }
    }
    if (base != SH_SINGLE && base != SH_NONE) {
        if (cacheready) {
            std::memcpy(hash_outputs, pc.hash_outputs, 32);
        } else {
            Bytes b;
            for (const auto& out : tx.vout) {
                put_i64(b, out.value);
                put_string(b, out.spk);
            }
            sha256d(b.data(), b.size(), hash_outputs);
        }
    } else if (base == SH_SINGLE && n_in < tx.vout.size()) {
        Bytes b = tx.vout[n_in].serialize();
        sha256d(b.data(), b.size(), hash_outputs);
    }
    Bytes s;
    put_u32(s, (u32)tx.version);
    s.insert(s.end(), hash_prevouts, hash_prevouts + 32);
    s.insert(s.end(), hash_sequence, hash_sequence + 32);
    s.insert(s.end(), tx.vin[n_in].prevout_hash, tx.vin[n_in].prevout_hash + 32);
    put_u32(s, tx.vin[n_in].prevout_n);
    put_string(s, script_code);
    put_i64(s, amount);
    put_u32(s, tx.vin[n_in].sequence);
    s.insert(s.end(), hash_outputs, hash_outputs + 32);
    put_u32(s, tx.locktime);
    put_u32(s, (u32)(i32)hash_type);
    sha256d(s.data(), s.size(), out);
    return s.size();
}

// Returns false on invalid hash type / SINGLE out of range.
inline bool bip341_sighash(const NTx& tx, size_t n_in, int hash_type,
                           int sigversion, bool annex_present,
                           const u8* annex_hash, const Bytes& tapleaf_hash,
                           u32 codeseparator_pos, u8 out[32]) {
    const Precomp& pc = tx.precomp;
    int ext_flag = sigversion == SV_TAPSCRIPT ? 1 : 0;
    Bytes s;
    s.push_back(0);  // epoch
    int output_type = hash_type == SH_DEFAULT ? SH_ALL : (hash_type & SH_OUTPUT_MASK);
    int input_type = hash_type & SH_INPUT_MASK;
    if (!(hash_type <= 0x03 || (hash_type >= 0x81 && hash_type <= 0x83)))
        return false;
    s.push_back(u8(hash_type));
    put_u32(s, (u32)tx.version);
    put_u32(s, tx.locktime);
    if (input_type != SH_ANYONECANPAY) {
        s.insert(s.end(), pc.prevouts_single, pc.prevouts_single + 32);
        s.insert(s.end(), pc.spent_amounts_single, pc.spent_amounts_single + 32);
        s.insert(s.end(), pc.spent_scripts_single, pc.spent_scripts_single + 32);
        s.insert(s.end(), pc.sequences_single, pc.sequences_single + 32);
    }
    if (output_type == SH_ALL)
        s.insert(s.end(), pc.outputs_single, pc.outputs_single + 32);
    u8 spend_type = u8((ext_flag << 1) + (annex_present ? 1 : 0));
    s.push_back(spend_type);
    if (input_type == SH_ANYONECANPAY) {
        s.insert(s.end(), tx.vin[n_in].prevout_hash, tx.vin[n_in].prevout_hash + 32);
        put_u32(s, tx.vin[n_in].prevout_n);
        Bytes so = pc.spent_outputs[n_in].serialize();
        put_bytes(s, so);
        put_u32(s, tx.vin[n_in].sequence);
    } else {
        put_u32(s, (u32)n_in);
    }
    if (annex_present) s.insert(s.end(), annex_hash, annex_hash + 32);
    if (output_type == SH_SINGLE) {
        if (n_in >= tx.vout.size()) return false;
        Bytes ob = tx.vout[n_in].serialize();
        u8 oh[32];
        sha256(ob.data(), ob.size(), oh);
        s.insert(s.end(), oh, oh + 32);
    }
    if (sigversion == SV_TAPSCRIPT) {
        s.insert(s.end(), tapleaf_hash.begin(), tapleaf_hash.end());
        s.push_back(0);  // key_version
        put_u32(s, codeseparator_pos);
    }
    TAG_TAPSIGHASH().hash(s.data(), s.size(), out);
    return true;
}

// --------------------------------------------------------------------------
// Checker with the deferral seam (models/batch.py DeferringSignatureChecker
// + core/interpreter.py TransactionSignatureChecker semantics).

// One check's parts, independent of where the bytes live (a Bytes triple
// on the interpreter's stack, the wire blob from Python, a session's
// arena) — the shared input shape of the oracle, the lane-prep and the
// digest cores. Part order: ecdsa pubkey|sig|msg; schnorr pk32|sig64|msg;
// tweak q32|internal32|tweak32.
struct PartsView {
    int kind;    // 0 ecdsa, 1 schnorr, 2 tweak
    int parity;  // tweak parity bit
    const u8* p0;
    i64 l0;
    const u8* p1;
    i64 l1;
    const u8* p2;
    i64 l2;
};

inline PartsView parts_of(int kind, int parity, const Bytes& a, const Bytes& b,
                          const Bytes& c) {
    return PartsView{kind,     parity,        a.data(), (i64)a.size(),
                     b.data(), (i64)b.size(), c.data(), (i64)c.size()};
}

struct Record {
    int kind;
    int parity;
    Bytes p0, p1, p2;
};

// The index-mode check list: every deduped check stored ONCE and addressed
// by its discovery index. The three parts lie back to back in one byte
// arena, offsets/lengths/verdict in one flat array, and the dedup table
// is open addressing over indices that compares against the arena — no
// per-check heap object, so recording is an append, publishing a verdict
// is a store, and releasing the store is three frees.
struct CheckStore {
    enum : u8 { V_UNKNOWN = 0, V_FALSE = 1, V_TRUE = 2 };
    struct Entry {
        u64 off;  // p0 starts here in `arena`; p1, p2 follow
        u64 hash;
        u32 l0, l1, l2;
        u8 kind, parity, verdict;
        u8 spec;  // 1: entered by CHECKMULTISIG's pre-recording, not a walk
    };
    std::vector<u8> arena;
    std::vector<Entry> entries;
    std::vector<i32> slots;  // power-of-two table of indices, -1 empty
    i64 spec_entries = 0;    // entries appended with `spec` set; monotone

    size_t size() const { return entries.size(); }

    // Valid until the next intern() (the arena may move).
    PartsView view(size_t i) const {
        const Entry& e = entries[i];
        const u8* p = arena.data() + e.off;
        return PartsView{e.kind,   e.parity,         p,
                         e.l0,     p + e.l0,         e.l1,
                         p + e.l0 + e.l1,            e.l2};
    }

    static u64 mix(u64 a, u64 b) {
        u128 m = (u128)a * b;
        return (u64)m ^ (u64)(m >> 64);
    }
    static u64 hash_part(u64 h, const u8* p, size_t n) {
        for (; n >= 8; p += 8, n -= 8) {
            u64 w;
            std::memcpy(&w, p, 8);
            h = mix(h ^ w, 0x9e3779b97f4a7c15ULL);
        }
        if (n) {
            u64 w = 0;
            std::memcpy(&w, p, n);
            h = mix(h ^ w, 0xc2b2ae3d27d4eb4fULL);
        }
        return h;
    }
    // Drawn once a process: a block's author chooses most of a check's
    // bytes, and with a known start could zero the running state (w == h)
    // and collide as many checks as the block holds.
    static u64 seed() {
        static const u64 s = [] {
            std::random_device rd;
            return ((u64)rd() << 32) ^ (u64)rd() ^ 0x2545f4914f6cdd1dULL;
        }();
        return s;
    }
    // In-process only (seeded, and word loads are host-endian); covers
    // exactly what `same` compares: kind, parity, the lengths, the bytes.
    static u64 hash_of(const PartsView& v) {
        u64 h = mix(seed() ^ (u64)v.kind ^ ((u64)v.parity << 8),
                    0x9e3779b97f4a7c15ULL);
        h = mix(h ^ (u64)v.l0 ^ ((u64)v.l1 << 21) ^ ((u64)v.l2 << 42),
                0xc2b2ae3d27d4eb4fULL);
        h = hash_part(h, v.p0, (size_t)v.l0);
        h = hash_part(h, v.p1, (size_t)v.l1);
        return hash_part(h, v.p2, (size_t)v.l2);
    }

    bool same(const Entry& e, u64 h, const PartsView& v) const {
        if (e.hash != h || e.kind != v.kind || e.parity != v.parity ||
            e.l0 != v.l0 || e.l1 != v.l1 || e.l2 != v.l2)
            return false;
        const u8* p = arena.data() + e.off;
        auto eq = [](const u8* x, const u8* y, size_t n) {
            return n == 0 || std::memcmp(x, y, n) == 0;
        };
        return eq(p, v.p0, e.l0) && eq(p + e.l0, v.p1, e.l1) &&
               eq(p + e.l0 + e.l1, v.p2, e.l2);
    }

    // Index of the entry equal to `v` (whose hash_of is `h`), or -1.
    i32 find(u64 h, const PartsView& v) const {
        if (slots.empty()) return -1;
        size_t mask = slots.size() - 1;
        for (size_t s = (size_t)h & mask;; s = (s + 1) & mask) {
            i32 i = slots[s];
            if (i < 0) return -1;
            if (same(entries[(size_t)i], h, v)) return i;
        }
    }

    void place(i32 i) {
        size_t mask = slots.size() - 1;
        size_t s = (size_t)entries[(size_t)i].hash & mask;
        while (slots[s] >= 0) s = (s + 1) & mask;
        slots[s] = i;
    }
    void grow() {
        slots.assign(slots.empty() ? 64 : slots.size() * 2, -1);
        for (size_t i = 0; i < entries.size(); i++) place((i32)i);
    }

    // find, or append `v` (its bytes copied once, verdict unknown, marked
    // `spec` when a pre-recording and not a key walk brings it).
    // `v` must not point into this store's own arena.
    i32 intern(u64 h, const PartsView& v, u8 spec = 0) {
        i32 at = find(h, v);
        if (at >= 0) return at;
        if ((entries.size() + 1) * 2 > slots.size()) grow();
        at = (i32)entries.size();
        entries.push_back(Entry{(u64)arena.size(), h, (u32)v.l0, (u32)v.l1,
                                (u32)v.l2, (u8)v.kind, (u8)v.parity,
                                V_UNKNOWN, spec});
        spec_entries += spec;
        arena.insert(arena.end(), v.p0, v.p0 + v.l0);
        arena.insert(arena.end(), v.p1, v.p1 + v.l1);
        arena.insert(arena.end(), v.p2, v.p2 + v.l2);
        place(at);
        return at;
    }

    size_t capacity_bytes() const {
        return arena.capacity() + entries.capacity() * sizeof(Entry) +
               slots.capacity() * sizeof(i32);
    }
    // Empty as a new store is, with the buffers it grew kept.
    void reset() {
        arena.clear();
        entries.clear();
        slots.clear();
        spec_entries = 0;
    }
};

// The buffers of retired check stores, kept for the next session. A block
// of 286,000 checks fills a 39 MB arena, an 11 MB entry list and a 4 MB
// table, and about as much again over the workers' scratch stores. Whether
// the allocator gave those pages back to the system at every release, so
// that the next connect faulted them in one by one, depended on the state
// of its heap, process by process: round one of the same block took 50 ms
// more in some processes than in others (PR 41). A retired store is
// emptied, its capacity kept, and parked here; stores under KEEP_FROM bytes
// are left to the allocator, and the pool holds MAX_BYTES at most.
struct StorePool {
    static constexpr size_t KEEP_FROM = 1u << 20;
    static constexpr size_t MAX_BYTES = 256u << 20;
    std::mutex mu;
    std::vector<CheckStore> idle;
    size_t bytes = 0;

    static StorePool& get() {
        static StorePool pool;
        return pool;
    }

    void give(CheckStore&& store) {
        size_t b = store.capacity_bytes();
        if (b < KEEP_FROM) return;
        store.reset();
        std::lock_guard<std::mutex> lock(mu);
        if (bytes + b > MAX_BYTES) return;
        bytes += b;
        idle.push_back(std::move(store));
    }

    // Hand `into`, a store that has allocated nothing yet, the largest
    // parked store (a session's own list) or the smallest (a worker's
    // scratch); leaves it as it is when nothing is parked.
    void take(CheckStore& into, bool largest) {
        if (into.capacity_bytes()) return;
        std::lock_guard<std::mutex> lock(mu);
        if (idle.empty()) return;
        size_t pick = 0;
        for (size_t i = 1; i < idle.size(); i++) {
            bool larger = idle[i].capacity_bytes() > idle[pick].capacity_bytes();
            if (larger == largest) pick = i;
        }
        bytes -= idle[pick].capacity_bytes();
        into = std::move(idle[pick]);
        idle.erase(idle.begin() + (std::ptrdiff_t)pick);
    }
};

// --- The native stage clock --------------------------------------------------
//
// What the host phases (`verifier.phases`) hold beneath the ctypes boundary,
// kept as the other counters here are: plain fields added to where the work
// runs and read out once, by nat_session_stages once a fixpoint and by
// nat_block_stages once a block. steady_clock is CLOCK_MONOTONIC, the clock of
// Python's time.perf_counter and of the phases' spans, so a stage lies inside
// its phase on one axis. Two reads a stage a call, two a worker a fan-out:
// never one an input, a lane or a coin.

inline i64 steady_ns() {
    return std::chrono::duration_cast<std::chrono::nanoseconds>(
               std::chrono::steady_clock::now().time_since_epoch()).count();
}

// A fan-out's own account (nat.cpp fan_out, which alone writes it), added a
// call, nanoseconds: `wall` from entry to the last join; `held` the width
// times that, the thread time the call held; `sum` and `max` the workers'
// busy time, first instruction to last, summed and the slowest's; `start_lag`
// from entry to the latest worker's first instruction (the pthread_create
// loop and the scheduler); `tail` from the last instruction of the worker
// that ended last to joined (the exits and the joins), so start_lag + tail
// never passes wall. Work on the caller's thread is one worker that starts
// at once: start_lag 0, sum = max = wall.
struct FanStats {
    enum : int { WALL = 0, HELD, SUM, MAX, START_LAG, TAIL, COUNT };
    i64 ns[COUNT] = {0, 0, 0, 0, 0, 0};
};

// Serial stages of N kinds: nanoseconds and times stamped.
template <int N>
struct StageClock {
    i64 ns[N] = {};
    i64 calls[N] = {};
    // Stage k ran from t0 to now; returns now, the next stage's start.
    i64 stamp(int k, i64 t0) {
        i64 t1 = steady_ns();
        ns[k] += t1 - t0;
        calls[k]++;
        return t1;
    }
    // out[0..N): the nanoseconds, out[N..2N): the times stamped.
    void read(i64* out) const {
        std::memcpy(out, ns, sizeof ns);
        std::memcpy(out + N, calls, sizeof calls);
    }
};

// Stamps stage k from `at` when it leaves scope: declared ahead of a call's
// locals, it counts their destructors into the call's last stage.
template <int N>
struct StageEnd {
    StageClock<N>& clock;
    int k;
    i64& at;
    ~StageEnd() { clock.stamp(k, at); }
};

struct Session {
    // Oracle verdicts published WITH their bytes (nat_session_add_known,
    // _batch: the wire driver and the tests' executable spec). Index mode
    // never writes here: its verdicts are bytes in `uniq`, and `lookup`
    // answers from both.
    std::map<std::string, bool> known;
    std::vector<Record> records;
    // --- Index-mode (session-resident uniq protocol) -----------------
    // The batch driver's fast path: instead of draining full record
    // bytes to Python, deduping there, and shipping them back for
    // digesting/lane-prep/publishing, the session keeps ONE deduped
    // check list (`uniq`, discovery order) and each verify call emits
    // only int32 indices into it (`rec_idx`). Lanes, cache digests and
    // verdict publication all read uniq in place — zero byte round-trips
    // across the ctypes bridge (the round-3 profile showed ~200 ms of a
    // 3.2k-input block replay in exactly that shuffling). The list IS the
    // oracle: a published verdict is entry i's verdict byte.
    bool index_mode = false;
    CheckStore uniq;
    std::vector<i32> rec_idx;  // per-call flat index stream
    // Read-only oracle for worker-scratch sessions (checkqueue.h analogue:
    // the threaded interpretation shards read the main session's verdicts;
    // scratch sessions collect checks in a store of their own and merge
    // serially).
    const Session* oracle = nullptr;

    // The one oracle read. Returns the verdict of the check `v` (hash `h`)
    // from whichever store holds it, V_UNKNOWN when neither has one.
    // *at is its index in the oracle's uniq list, or -1.
    u8 lookup(u64 h, const PartsView& v, i32* at) const {
        const Session& o = oracle ? *oracle : *this;
        *at = o.uniq.find(h, v);
        if (*at >= 0) return o.uniq.entries[(size_t)*at].verdict;
        if (!o.known.empty()) {
            auto it = o.known.find(key(v));
            if (it != o.known.end())
                return it->second ? CheckStore::V_TRUE : CheckStore::V_FALSE;
        }
        return CheckStore::V_UNKNOWN;
    }

    // Publish a verdict with its bytes: into the uniq entry when the check
    // is one, else into `known` — so a check lives in one store and the
    // last write wins whichever protocol made it.
    void set_known(const PartsView& v, bool result) {
        i32 at = uniq.size() ? uniq.find(CheckStore::hash_of(v), v) : -1;
        if (at >= 0)
            uniq.entries[(size_t)at].verdict =
                result ? CheckStore::V_TRUE : CheckStore::V_FALSE;
        else
            known[key(v)] = result;
    }

    // Index mode: the uniq index of an oracle miss, deduped. `at` is what
    // `lookup` found (a recorded, still-unpublished check keeps its index).
    i32 index_record(u64 h, const PartsView& v, i32 at, u8 spec = 0) {
        return (at >= 0 && !oracle) ? at : uniq.intern(h, v, spec);
    }
    // Speculative CHECKMULTISIG pairings: every (sig, key) pair the cursor
    // walk could reach (key-index minus sig-index in [0, nkeys-nsigs]) is
    // pre-recorded here so ONE device dispatch answers every oracle read a
    // re-interpretation can make — misaligned multisig resolves without a
    // second host->device round-trip. Kept apart from `records` so the
    // optimistic-verdict judgment stays exact (a false speculative pair
    // must not reject a verdict whose own checks all held).
    std::vector<Record> spec;
    std::set<std::string> spec_seen;
    int unknown = 0;
    // ECDSA message digests hashed by this session's interpretations, and
    // reads of one that an earlier pairing of the same CHECKMULTISIG had
    // made (eval.hpp MultisigSigs); monotone, worker scratches summed in.
    i64 sighash_computed = 0;
    i64 sighash_reused = 0;
    // What those digests cost, by kind (legacy, bip143): the bytes of the
    // preimages hashed and the nanoseconds of thread time from building
    // one to its double hash; monotone and summed like the two above.
    enum : int { SK_LEGACY = 0, SK_BIP143, SK_COUNT };
    i64 sighash_bytes[SK_COUNT] = {0, 0};
    i64 sighash_ns[SK_COUNT] = {0, 0};
    // Blanked templates its legacy digests laid down (one a transaction, a
    // second where NONE or SINGLE is also signed), digests hashed from one,
    // and those of them that started from one of its grid points
    // (LegacyTemplate::EV_BUILT, EV_SERVED, EV_RESUMED); monotone and summed
    // alike.
    i64 sighash_template[LegacyTemplate::EV_COUNT] = {0, 0, 0};
    // The session's three calls that fan out, each with the fan-out's own
    // account (FanStats) and its serial stages, which tile the call: their
    // sum is the C call's duration less a few clock reads. interpret
    // (nat_verify_inputs_idx): setup, the scratch sessions and the pool;
    // workers, around the fan-out; merge, the scratches' counters summed
    // and the serial merge in index order, to the return. lanes
    // (nat_session_uniq_lanes): order, the serial lanes_order loop; shards,
    // around the fan-out. digests (nat_session_uniq_digests): shards.
    // `max` times the width over `sum` says how level a call's workers
    // ended (1.0: every one as long as the slowest). Monotone.
    enum : int { FAN_INTERPRET = 0, FAN_LANES, FAN_DIGESTS, FAN_COUNT };
    FanStats fans[FAN_COUNT];
    enum : int {
        ST_INTERPRET_SETUP = 0, ST_INTERPRET_WORKERS, ST_INTERPRET_MERGE,
        ST_LANES_ORDER, ST_LANES_SHARDS, ST_DIGESTS_SHARDS, ST_COUNT
    };
    StageClock<ST_COUNT> stages;
    // Taproot's hashing by this session's interpretations, monotone and
    // summed like the two above: BIP 341 digests (key path and tapscript),
    // and the commitment's tagged hashes (TapLeaf, TapBranch, TapTweak).
    enum : int { TH_SIGHASH = 0, TH_LEAF, TH_BRANCH, TH_TWEAK, TH_COUNT };
    i64 taproot_hashes[TH_COUNT] = {0, 0, 0, 0};
    // Lanes nat_session_uniq_lanes prepped out of this session's uniq
    // list, by the check's kind (ecdsa, schnorr, tweak).
    i64 lanes_by_kind[3] = {0, 0, 0};
    // (signature, key) pairings CHECKMULTISIG's cursor walk tried in each
    // interpretation of the newest verify call, by the input's position in
    // that call (workers write their own slots): the number Core's own
    // walk verifies. The next call overwrites it; the driver adds an
    // input's count when it accepts that interpretation's verdict.
    std::vector<i64> call_walk;

    static std::string key(const PartsView& v) {
        std::string k;
        k.push_back(char(v.kind));
        k.push_back(char(v.parity));
        auto add = [&](const u8* p, i64 len) {
            u64 n = (u64)len;
            for (int i = 0; i < 8; i++) k.push_back(char(u8(n >> (8 * i))));
            k.append(reinterpret_cast<const char*>(p), (size_t)len);
        };
        add(v.p0, v.l0);
        add(v.p1, v.l1);
        add(v.p2, v.l2);
        return k;
    }
};

struct ExecData {
    bool annex_present = false;
    u8 annex_hash[32] = {0};
    bool tapleaf_hash_init = false;
    Bytes tapleaf_hash;
    u32 codeseparator_pos = 0xFFFFFFFF;
    bool validation_weight_left_init = false;
    i64 validation_weight_left = 0;
};

enum : int { MODE_DEFER = 0, MODE_EXACT = 1 };

struct Checker {
    const NTx* tx;
    size_t n_in;
    i64 amount;
    int mode;
    Session* sess;  // used in MODE_DEFER
    // Pairings the CHECKMULTISIG walks of this interpretation tried
    // (eval.hpp counts; run_verify_input hands the total to its caller).
    i64 walk_pairings = 0;

    // raw curve resolution: oracle -> record-optimistic (defer) or native
    // verify (exact)
    bool resolve(int kind, int parity, const Bytes& a, const Bytes& b,
                 const Bytes& c) {
        if (mode == MODE_EXACT) {
            if (kind == 0)
                return verify_ecdsa(a.data(), a.size(), b.data(), b.size(), c.data());
            if (kind == 1) return verify_schnorr(a.data(), b.data(), c.data());
            return tweak_add_check(a.data(), parity, b.data(), c.data());
        }
        PartsView v = parts_of(kind, parity, a, b, c);
        u64 h = CheckStore::hash_of(v);
        i32 at;
        u8 verdict = sess->lookup(h, v, &at);
        if (verdict != CheckStore::V_UNKNOWN)
            return verdict == CheckStore::V_TRUE;
        sess->unknown++;
        if (sess->index_mode)
            sess->rec_idx.push_back(sess->index_record(h, v, at));
        else
            sess->records.push_back(Record{kind, parity, a, b, c});
        return true;
    }

    // Structural early-false gates shared by check and speculate: a sig/key
    // failing these never reaches the curve, so there is nothing to defer.
    static bool pubkey_plausible(const Bytes& pubkey) {
        if (pubkey.empty()) return false;
        u8 p0 = pubkey[0];
        if (p0 == 2 || p0 == 3) return pubkey.size() == 33;
        if (p0 == 4 || p0 == 6 || p0 == 7) return pubkey.size() == 65;
        return false;
    }

    static bool ec_check_plausible(const Bytes& sig, const Bytes& pubkey) {
        return !sig.empty() && pubkey_plausible(pubkey);
    }

    // The ECDSA message digest of `script_code` under `hash_type`: a
    // function of the signature's hash-type byte and never of the key.
    void ecdsa_sighash(int hash_type, const Bytes& script_code, int sigversion,
                       u8 out[32]) {
        auto t0 = std::chrono::steady_clock::now();
        int kind = sigversion == SV_WITNESS_V0 ? Session::SK_BIP143 : Session::SK_LEGACY;
        size_t hashed =
            kind == Session::SK_BIP143
                ? bip143_sighash(script_code, *tx, n_in, hash_type, amount, out)
                : legacy_sighash(script_code, *tx, n_in, hash_type, out,
                                 sess ? sess->sighash_template : nullptr);
        if (!sess) return;
        sess->sighash_computed++;
        sess->sighash_bytes[kind] += (i64)hashed;
        sess->sighash_ns[kind] += std::chrono::duration_cast<std::chrono::nanoseconds>(
                                      std::chrono::steady_clock::now() - t0).count();
    }

    // OP_CHECKSIG's check: one signature, one key, one digest.
    bool check_ecdsa_signature(const Bytes& sig, const Bytes& pubkey,
                               const Bytes& script_code, int sigversion) {
        if (!ec_check_plausible(sig, pubkey)) return false;
        u8 sighash[32];
        ecdsa_sighash(sig.back(), script_code, sigversion, sighash);
        Bytes sig_body(sig.begin(), sig.end() - 1), msg(sighash, sighash + 32);
        return resolve(0, 0, pubkey, sig_body, msg);
    }

    // Speculative CHECKMULTISIG pre-recording of one (signature, key)
    // pairing; the op's MultisigSigs (eval.hpp) made `sig_body` and `msg`.
    void speculate_ecdsa_record(const Bytes& pubkey, const Bytes& sig_body,
                                const Bytes& msg) {
        if (!pubkey_plausible(pubkey)) return;
        PartsView v = parts_of(0, 0, pubkey, sig_body, msg);
        u64 h = CheckStore::hash_of(v);
        i32 at;
        if (sess->lookup(h, v, &at) != CheckStore::V_UNKNOWN) return;
        if (sess->index_mode) {
            // Resolve-only: dedup into uniq WITHOUT emitting a rec_idx
            // entry, so a speculative pair can never affect an
            // optimistic verdict (same contract as the spec vector).
            // Marked `spec` where it is new (uniq.spec_entries counts them;
            // wire mode counts what it drains from `spec`).
            sess->index_record(h, v, at, 1);
            return;
        }
        if (!sess->spec_seen.insert(Session::key(v)).second) return;
        sess->spec.push_back(Record{0, 0, pubkey, sig_body, msg});
    }

    // returns ok; on hard failure sets *err
    bool check_schnorr_signature(const Bytes& sig_in, const Bytes& pubkey,
                                 int sigversion, ExecData& execdata, i32* err) {
        Bytes sig = sig_in;
        if (sig.size() != 64 && sig.size() != 65) {
            *err = SE_SCHNORR_SIG_SIZE;
            return false;
        }
        int hash_type = SH_DEFAULT;
        if (sig.size() == 65) {
            hash_type = sig.back();
            sig.pop_back();
            if (hash_type == SH_DEFAULT) {
                *err = SE_SCHNORR_SIG_HASHTYPE;
                return false;
            }
        }
        u8 sighash[32];
        if (!bip341_sighash(*tx, n_in, hash_type, sigversion,
                            execdata.annex_present, execdata.annex_hash,
                            execdata.tapleaf_hash, execdata.codeseparator_pos,
                            sighash)) {
            *err = SE_SCHNORR_SIG_HASHTYPE;
            return false;
        }
        if (sess) sess->taproot_hashes[Session::TH_SIGHASH]++;
        Bytes msg(sighash, sighash + 32);
        if (!resolve(1, 0, pubkey, sig, msg)) {
            *err = SE_SCHNORR_SIG;
            return false;
        }
        return true;
    }

    bool check_lock_time(i64 lock_time) {
        i64 tx_lock = (i64)tx->locktime;
        if (!((tx_lock < LOCKTIME_THRESHOLD && lock_time < LOCKTIME_THRESHOLD) ||
              (tx_lock >= LOCKTIME_THRESHOLD && lock_time >= LOCKTIME_THRESHOLD)))
            return false;
        if (lock_time > tx_lock) return false;
        if (tx->vin[n_in].sequence == SEQUENCE_FINAL) return false;
        return true;
    }

    bool check_sequence(i64 sequence) {
        u32 tx_sequence = tx->vin[n_in].sequence;
        if ((u32)tx->version < 2) return false;
        if (tx_sequence & SEQ_DISABLE) return false;
        u32 mask = SEQ_TYPE | SEQ_MASK;
        u32 tx_masked = tx_sequence & mask;
        u32 seq_masked = (u32)sequence & mask;
        if (!((tx_masked < SEQ_TYPE && seq_masked < SEQ_TYPE) ||
              (tx_masked >= SEQ_TYPE && seq_masked >= SEQ_TYPE)))
            return false;
        if (seq_masked > tx_masked) return false;
        return true;
    }

    bool verify_taproot_tweak(const Bytes& q, int parity, const Bytes& p,
                              const Bytes& t) {
        return resolve(2, parity, q, p, t);
    }
};

// --------------------------------------------------------------------------
// Encoding checks (interpreter.cpp:107-227 twins; byte-level only).

inline bool is_valid_signature_encoding(const Bytes& sig) {
    if (sig.size() < 9 || sig.size() > 73) return false;
    if (sig[0] != 0x30) return false;
    if (sig[1] != sig.size() - 3) return false;
    size_t lenR = sig[3];
    if (5 + lenR >= sig.size()) return false;
    size_t lenS = sig[5 + lenR];
    if (lenR + lenS + 7 != sig.size()) return false;
    if (sig[2] != 0x02) return false;
    if (lenR == 0) return false;
    if (sig[4] & 0x80) return false;
    if (lenR > 1 && sig[4] == 0x00 && !(sig[5] & 0x80)) return false;
    if (sig[lenR + 4] != 0x02) return false;
    if (lenS == 0) return false;
    if (sig[lenR + 6] & 0x80) return false;
    if (lenS > 1 && sig[lenR + 6] == 0x00 && !(sig[lenR + 7] & 0x80)) return false;
    return true;
}

inline bool is_low_der_signature(const Bytes& sig) {
    // strict-DER already checked by the caller; parse (r, s) laxly and
    // test s <= n/2 (pubkey.cpp:301-308 CheckLowS).
    Sc r, s;
    if (!parse_der_lax(sig.data(), sig.size() - 1, &r, &s)) return false;
    return !sc_is_high(s);
}

inline bool is_compressed_or_uncompressed_pubkey(const Bytes& pk) {
    if (pk.size() < 33) return false;
    if (pk[0] == 0x04) return pk.size() == 65;
    if (pk[0] == 0x02 || pk[0] == 0x03) return pk.size() == 33;
    return false;
}

inline bool is_compressed_pubkey(const Bytes& pk) {
    return pk.size() == 33 && (pk[0] == 0x02 || pk[0] == 0x03);
}

inline i32 check_signature_encoding(const Bytes& sig, u32 flags) {
    if (sig.empty()) return SE_OK;
    if (flags & (F_DERSIG | F_LOW_S | F_STRICTENC)) {
        if (!is_valid_signature_encoding(sig)) return SE_SIG_DER;
    }
    if (flags & F_LOW_S) {
        if (!is_valid_signature_encoding(sig)) return SE_SIG_DER;
        if (!is_low_der_signature(sig)) return SE_SIG_HIGH_S;
    }
    if (flags & F_STRICTENC) {
        int hash_type = sig.back() & ~0x80;
        if (hash_type < 1 || hash_type > 3) return SE_SIG_HASHTYPE;
    }
    return SE_OK;
}

inline i32 check_pubkey_encoding(const Bytes& pk, u32 flags, int sigversion) {
    if ((flags & F_STRICTENC) && !is_compressed_or_uncompressed_pubkey(pk))
        return SE_PUBKEYTYPE;
    if ((flags & F_WITNESS_PUBKEYTYPE) && sigversion == SV_WITNESS_V0 &&
        !is_compressed_pubkey(pk))
        return SE_WITNESS_PUBKEYTYPE;
    return SE_OK;
}

}  // namespace nat

// Coverage-guided differential fuzzer for the native consensus core.
//
// The reference tree ships libFuzzer harnesses over exactly this risk
// surface (depend/bitcoin/src/test/fuzz/script.cpp, decode_tx.cpp with
// FuzzedDataProvider.h). This image's toolchain has no clang/libFuzzer,
// so the engine is built in: native/nat.cpp is compiled with
// -fsanitize-coverage=trace-pc (only the library — the engine itself is
// uninstrumented or the callback would recurse), edges hash into an
// AFL-style bitmap, and an in-process mutation loop (bitflips, byte ops,
// chunk dup/del, splices, interesting values) keeps inputs that reach
// new coverage. fuzz/run.sh builds it under ASAN+UBSAN so memory bugs
// abort loudly.
//
// The harness drives ONLY the exported C ABI (the real attack surface):
//  0: transaction codec — parse/serialize fixpoint, wtxid stability
//  1: block codec — parse, per-tx ids, accounting on an empty view
//  2: script verify — the EXACT engine's verdict must equal the
//     DEFERRING engine's verdict after its recorded checks are resolved
//     by the host-exact curve functions and re-interpreted to a fixpoint
//     (the two drive modes of native/eval.hpp must agree on EVERY input);
//     the libbitcoinconsensus entry additionally must never crash.

#include <algorithm>
#include <chrono>
#include <cstdint>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <dirent.h>
#include <random>
#include <string>
#include <vector>

using u8 = uint8_t;
using i32 = int32_t;
using i64 = int64_t;

extern "C" {
// nat.cpp exports (typed as the bridge types them)
void* nat_tx_parse(const u8*, i64);
void nat_tx_free(void*);
i64 nat_tx_ser_size(void*);
i32 nat_tx_n_inputs(void*);
void nat_tx_wtxid(void*, u8*);
void nat_tx_precompute(void*);
i64 nat_tx_serialize_size(void*, i32);
void nat_tx_serialize(void*, i32, u8*);
void* nat_block_parse(const u8*, i64);
void nat_block_free(void*);
i32 nat_block_n_tx(void*);
void nat_block_txid(void*, i32, u8*);
i32 nat_block_check(void*, i32, const u8*, i32);
i32 nat_block_accounting(void*, void*, i64, i32, const u8*, i64);
void* nat_view_new();
void nat_view_free(void*);
void* nat_session_new();
void nat_session_free(void*);
void nat_session_add_known(void*, i32, i32, const u8*, i64, const u8*, i64,
                           const u8*, i64, i32);
i32 nat_session_records_count(void*);
void nat_session_records_meta(void*, i32*, i32*, i64*);
i64 nat_session_records_bytes(void*);
void nat_session_records_data(void*, u8*);
i32 nat_verify_input(void*, void*, i32, i64, const u8*, i64, i32, i32, i32*,
                     i32*);
void nat_verify_inputs_idx(void*, void**, const i32*, const i64*, const u8*,
                           const i64*, const i32*, i32, i32, i32*, i32*, i32*,
                           i64*);
i32 nat_session_uniq_count(void*);
void nat_session_uniq_lanes(void*, const i32*, i32, i32, u8*, i32*, i32*, i32*,
                            i32*, i32*, i32*);
void nat_session_uniq_digests(void*, const u8*, i64, const i32*, i32, i32,
                              u8*);
void nat_session_publish_uniq(void*, const i32*, i32, const i32*);
i32 nat_session_uniq_host_verify(void*, i32);
int nat_verify_ecdsa(const u8*, i64, const u8*, i64, const u8*);
int nat_verify_schnorr(const u8*, const u8*, const u8*);
int nat_tweak_add_check(const u8*, i32, const u8*, const u8*);
int bitcoinconsensus_verify_script_with_amount(const u8*, unsigned, int64_t,
                                               const u8*, unsigned, unsigned,
                                               unsigned, i32*);
// provided to the instrumented object
void __sanitizer_cov_trace_pc();
}

// --- coverage bitmap -------------------------------------------------------
static uint8_t g_map[1 << 16];
static uintptr_t g_prev;

extern "C" void __sanitizer_cov_trace_pc() {
    uintptr_t pc = (uintptr_t)__builtin_return_address(0);
    uintptr_t h = (pc >> 4) ^ (pc << 8);
    g_map[(h ^ g_prev) & 0xFFFF]++;
    g_prev = (h >> 1) & 0xFFFF;
}

static std::vector<uint8_t> g_seen(1 << 16, 0);

static bool new_coverage() {
    bool fresh = false;
    for (size_t i = 0; i < g_seen.size(); i++) {
        if (g_map[i] && !g_seen[i]) {
            g_seen[i] = 1;
            fresh = true;
        }
    }
    return fresh;
}

// --- targets ---------------------------------------------------------------
static void target_tx_codec(const uint8_t* d, size_t n) {
    void* tx = nat_tx_parse(d, (i64)n);
    if (!tx) return;  // malformed input: rejection is the correct outcome
    i64 sz = nat_tx_serialize_size(tx, 1);
    if (sz != nat_tx_ser_size(tx)) {
        std::fprintf(stderr, "FUZZ BUG: ser_size mismatch\n");
        std::abort();
    }
    std::vector<u8> ser((size_t)sz);
    nat_tx_serialize(tx, 1, ser.data());
    // ser_size is what the reader consumed: those bytes are the tx.
    if ((size_t)sz > n || std::memcmp(ser.data(), d, (size_t)sz) != 0) {
        std::fprintf(stderr, "FUZZ BUG: consumed bytes != serialization\n");
        std::abort();
    }
    void* tx2 = nat_tx_parse(ser.data(), sz);
    if (!tx2) {
        std::fprintf(stderr, "FUZZ BUG: reparse of own serialization failed\n");
        std::abort();
    }
    i64 sz2 = nat_tx_serialize_size(tx2, 1);
    std::vector<u8> ser2((size_t)sz2);
    nat_tx_serialize(tx2, 1, ser2.data());
    if (ser2 != ser) {
        std::fprintf(stderr, "FUZZ BUG: serialize fixpoint broken\n");
        std::abort();
    }
    u8 id1[32], id2[32];
    nat_tx_wtxid(tx, id1);
    nat_tx_wtxid(tx2, id2);
    if (std::memcmp(id1, id2, 32) != 0) {
        std::fprintf(stderr, "FUZZ BUG: wtxid unstable across reparse\n");
        std::abort();
    }
    nat_tx_free(tx);
    nat_tx_free(tx2);
}

static void target_block_codec(const uint8_t* d, size_t n) {
    void* blk = nat_block_parse(d, (i64)n);
    if (!blk) return;
    i32 ntx = nat_block_n_tx(blk);
    u8 id[32];
    for (i32 i = 0; i < ntx; i++) nat_block_txid(blk, i, id);
    u8 limit[32];
    std::memset(limit, 0xFF, 32);
    nat_block_check(blk, 1, limit, 1);  // must not crash on any shape
    void* view = nat_view_new();
    const u8 salt[4] = {1, 2, 3, 4};
    nat_block_accounting(blk, view, 500000, (1 << 0) | (1 << 11), salt, 4);
    nat_view_free(view);
    nat_block_free(blk);
}

// Split input into (flags, amount, spk, tx); run both interpreter drive
// modes; verdicts must agree after oracle resolution.
static void target_verify_differential(const uint8_t* d, size_t n) {
    if (n < 8) return;
    i32 flags = (i32)(((uint32_t)d[0] | ((uint32_t)d[1] << 8)) & 0x1FFFFu);
    i64 amount = (i64)(((uint64_t)d[2] << 8) | d[3]) * 1000;
    size_t spk_len = std::min<size_t>(d[4], n - 5);
    const uint8_t* spk = d + 5;
    const uint8_t* txb = d + 5 + spk_len;
    size_t tx_len = n - 5 - spk_len;

    void* tx = nat_tx_parse(txb, (i64)tx_len);
    if (!tx) return;
    i32 nin_count = nat_tx_n_inputs(tx);
    if (nin_count == 0) {
        nat_tx_free(tx);
        return;
    }
    i32 n_in = (i32)(d[2] % nin_count);
    nat_tx_precompute(tx);

    i32 err_exact, unk;
    i32 ok_exact = nat_verify_input(nullptr, tx, n_in, amount, spk,
                                    (i64)spk_len, flags, /*exact*/ 1,
                                    &err_exact, &unk);

    void* sess = nat_session_new();
    i32 ok_def = 0, err_def = 0;
    bool resolved = false;
    for (int round = 0; round < 64; round++) {
        i32 unknown = 0;
        ok_def = nat_verify_input(sess, tx, n_in, amount, spk, (i64)spk_len,
                                  flags, /*defer*/ 0, &err_def, &unknown);
        if (unknown == 0) {
            resolved = true;
            break;
        }
        i32 cnt = nat_session_records_count(sess);
        std::vector<i32> kinds(cnt), parities(cnt);
        std::vector<i64> lens(3 * (size_t)cnt);
        nat_session_records_meta(sess, kinds.data(), parities.data(),
                                 lens.data());
        std::vector<u8> blob((size_t)nat_session_records_bytes(sess));
        nat_session_records_data(sess, blob.data());
        size_t pos = 0;
        for (i32 i = 0; i < cnt; i++) {
            const u8* p0 = blob.data() + pos;
            const u8* p1 = p0 + lens[3 * i];
            const u8* p2 = p1 + lens[3 * i + 1];
            pos += (size_t)(lens[3 * i] + lens[3 * i + 1] + lens[3 * i + 2]);
            int ok;
            if (kinds[i] == 0)
                ok = nat_verify_ecdsa(p0, lens[3 * i], p1, lens[3 * i + 1], p2);
            else if (kinds[i] == 1)
                ok = nat_verify_schnorr(p0, p1, p2);
            else
                ok = nat_tweak_add_check(p0, parities[i], p1, p2);
            nat_session_add_known(sess, kinds[i], parities[i], p0,
                                  lens[3 * i], p1, lens[3 * i + 1], p2,
                                  lens[3 * i + 2], ok);
        }
    }
    // An input that still defers after the round cap (a crafted >64-stage
    // check chain) has no complete deferring verdict to compare — the
    // production drivers fall back to the exact engine there, so only
    // resolved verdicts are differential.
    if (resolved &&
        (ok_def != ok_exact || (!ok_def && err_def != err_exact))) {
        std::fprintf(stderr,
                     "FUZZ BUG: defer/exact divergence ok=%d/%d err=%d/%d\n",
                     ok_def, ok_exact, err_def, err_exact);
        std::abort();
    }
    nat_session_free(sess);

    // Index-mode leg: the same input through the session-resident
    // protocol. Each round's new checks are read back out of the arena
    // (lanes, digests, the exact host verdict) and published by index; the
    // fixpoint's verdict must be the exact one too.
    void* isess = nat_session_new();
    void* txs[1] = {tx};
    i64 spk_offs[2] = {0, (i64)spk_len};
    i64 bounds[2];
    i32 ok_idx = 0, err_idx = 0, published = 0;
    bool resolved_idx = false;
    for (int round = 0; round < 64; round++) {
        i32 unknown = 0;
        nat_verify_inputs_idx(isess, txs, &n_in, &amount, spk, spk_offs, &flags,
                              1, 1, &ok_idx, &err_idx, &unknown, bounds);
        if (unknown == 0) {
            resolved_idx = true;
            break;
        }
        i32 fresh = nat_session_uniq_count(isess) - published;
        if (fresh <= 0) break;  // a miss must be a new, unpublished entry
        std::vector<i32> idxs((size_t)fresh), verdicts((size_t)fresh);
        for (i32 j = 0; j < fresh; j++) {
            idxs[(size_t)j] = published + j;
            verdicts[(size_t)j] =
                nat_session_uniq_host_verify(isess, published + j);
        }
        std::vector<u8> fields((size_t)fresh * 128), digests((size_t)fresh * 32);
        std::vector<i32> cols((size_t)fresh * 6);
        nat_session_uniq_lanes(isess, idxs.data(), fresh, 1, fields.data(),
                               cols.data(), cols.data() + fresh,
                               cols.data() + 2 * fresh, cols.data() + 3 * fresh,
                               cols.data() + 4 * fresh, cols.data() + 5 * fresh);
        nat_session_uniq_digests(isess, spk, (i64)spk_len, idxs.data(), fresh,
                                 1, digests.data());
        nat_session_publish_uniq(isess, idxs.data(), fresh, verdicts.data());
        published += fresh;
    }
    if (resolved_idx &&
        (ok_idx != ok_exact || (!ok_idx && err_idx != err_exact))) {
        std::fprintf(stderr,
                     "FUZZ BUG: index/exact divergence ok=%d/%d err=%d/%d\n",
                     ok_idx, ok_exact, err_idx, err_exact);
        std::abort();
    }
    nat_session_free(isess);

    // The libbitcoinconsensus entry must never crash (verdict may differ:
    // it applies the flag gate + exact-size checks first).
    if (!(flags & ~0xE15)) {
        i32 err;
        bitcoinconsensus_verify_script_with_amount(
            spk, (unsigned)spk_len, amount, txb, (unsigned)tx_len,
            (unsigned)n_in, (unsigned)flags, &err);
    }
    nat_tx_free(tx);
}

static void run_one(const std::vector<uint8_t>& in) {
    if (in.empty()) return;
    g_prev = 0;
    const uint8_t* d = in.data() + 1;
    size_t n = in.size() - 1;
    switch (in[0] % 3) {
        case 0: target_tx_codec(d, n); break;
        case 1: target_block_codec(d, n); break;
        default: target_verify_differential(d, n); break;
    }
}

// --- mutation engine -------------------------------------------------------
static std::mt19937_64 g_rng(0xC0FFEE);

static std::vector<uint8_t> mutate(
    const std::vector<std::vector<uint8_t>>& corpus) {
    std::vector<uint8_t> x = corpus[g_rng() % corpus.size()];
    int n_mut = 1 + (int)(g_rng() % 8);
    static const int64_t interesting[] = {0, 1, -1, 0xFF, 0xFFFF, 253, 254,
                                          255, 0x7FFFFFFF, 0x80};
    for (int m = 0; m < n_mut && !x.empty(); m++) {
        switch (g_rng() % 6) {
            case 0:  // bitflip
                x[g_rng() % x.size()] ^= (uint8_t)(1u << (g_rng() % 8));
                break;
            case 1:  // random byte
                x[g_rng() % x.size()] = (uint8_t)g_rng();
                break;
            case 2: {  // interesting value (LE, up to 4 bytes)
                size_t pos = g_rng() % x.size();
                int64_t v = interesting[g_rng() % 10];
                for (size_t i = 0; i < 4 && pos + i < x.size(); i++)
                    x[pos + i] = (uint8_t)(v >> (8 * i));
                break;
            }
            case 3: {  // chunk delete
                if (x.size() < 2) break;
                size_t a = g_rng() % x.size();
                size_t len = 1 + g_rng() % std::min<size_t>(16, x.size() - a);
                x.erase(x.begin() + a, x.begin() + a + (long)len);
                break;
            }
            case 4: {  // chunk duplicate
                if (x.size() > (1 << 16)) break;
                size_t a = g_rng() % x.size();
                size_t len = 1 + g_rng() % std::min<size_t>(16, x.size() - a);
                std::vector<uint8_t> chunk(x.begin() + a,
                                           x.begin() + a + (long)len);
                x.insert(x.begin() + (long)a, chunk.begin(), chunk.end());
                break;
            }
            default: {  // splice with another corpus entry
                const auto& other = corpus[g_rng() % corpus.size()];
                if (other.empty()) break;
                size_t a = g_rng() % x.size();
                size_t b = g_rng() % other.size();
                x.resize(a);
                x.insert(x.end(), other.begin() + (long)b, other.end());
                break;
            }
        }
    }
    if (x.empty()) x.push_back(0);
    return x;
}

int main(int argc, char** argv) {
    int seconds = argc > 1 ? std::atoi(argv[1]) : 30;
    const char* seed_dir = argc > 2 ? argv[2] : nullptr;

    std::vector<std::vector<uint8_t>> corpus;
    if (seed_dir) {
        if (DIR* dir = opendir(seed_dir)) {
            while (dirent* e = readdir(dir)) {
                std::string path = std::string(seed_dir) + "/" + e->d_name;
                if (FILE* f = std::fopen(path.c_str(), "rb")) {
                    std::vector<uint8_t> buf;
                    uint8_t tmp[4096];
                    size_t got;
                    while ((got = std::fread(tmp, 1, sizeof tmp, f)) > 0)
                        buf.insert(buf.end(), tmp, tmp + got);
                    std::fclose(f);
                    if (!buf.empty() && buf.size() < (1 << 18))
                        corpus.push_back(std::move(buf));
                }
            }
            closedir(dir);
        }
    }
    if (corpus.empty()) corpus.push_back({0});

    for (const auto& s : corpus) {  // replay seeds, record their coverage
        std::memset(g_map, 0, sizeof g_map);
        run_one(s);
        new_coverage();
    }

    auto t0 = std::chrono::steady_clock::now();
    uint64_t execs = 0, finds = 0;
    while (std::chrono::duration_cast<std::chrono::seconds>(
               std::chrono::steady_clock::now() - t0)
               .count() < seconds) {
        std::vector<uint8_t> x = mutate(corpus);
        std::memset(g_map, 0, sizeof g_map);
        run_one(x);
        execs++;
        if (new_coverage()) {
            corpus.push_back(std::move(x));
            finds++;
        }
    }
    std::printf(
        "fuzz_nat: %llu execs, %zu corpus entries (%llu found), 0 crashes\n",
        (unsigned long long)execs, corpus.size(), (unsigned long long)finds);
    return 0;
}

// Salted-digest LRU set: where models/sigcache.py's two success caches
// keep their keys when this library is loaded.
//
// The contract is the Python set's (an OrderedDict behind a lock, which
// stays in sigcache.py as the fallback and as the reference the tests run
// this against), key for key: a probe that hits without `erase` and a
// re-add are freshness touches, an insert past `max_entries` evicts the
// oldest inside the same call, and the five counters move as `n` single
// calls would move them. A bulk call takes the mutex once and walks its
// blob here, with the GIL released (ctypes drops it around the call), so
// nothing under the lock can call back into Python.
//
// Layout: a slab of {key, prev, next} nodes linked oldest to newest, free
// nodes chained through `next`, and an open-addressing table of
// {tag, node} slots at a load of a half at most. The tag is the upper half
// of the key's hash and gives the slot's home, so a stranger is told apart
// and a table is rebuilt without touching a node. Both grow with what the
// set holds (a fresh set owns one 16-slot table): a 1 Mi-entry cache made
// before every connect costs what it fills, never its bound.

#pragma once

#include <algorithm>
#include <cstdint>
#include <cstring>
#include <mutex>
#include <vector>

#include "sha256.hpp"

namespace nat {

class LruSet {
  public:
    enum { HITS, MISSES, INSERTIONS, EVICTIONS, ERASES, N_COUNTERS };

    explicit LruSet(int64_t max_entries)
        // Node indices are 32 bits: a bound past them is never reached.
        : cap_(max_entries < 1 ? 1
               : (u64)max_entries > MAX_NODES ? MAX_NODES
                                              : (u64)max_entries) {
        rebuild(4);
    }

    // Probe keys[0..n): present[j] = 1 where key j is in the set. A hit is
    // erased with `erase`, else moved to the newest end. `fabricated` is the
    // fault plan's poisoned probe (resilience/faults.py): an absent key
    // counts as a hit, the set untouched. Returns the size afterwards.
    int64_t probe(const u8* keys, int64_t n, bool erase, bool fabricated,
                  u8* present, int64_t* n_present) {
        std::lock_guard<std::mutex> hold(mu_);
        int64_t found = 0;
        for (int64_t j = 0; j < n; j++) {
            const u8* k = keys + 32 * j;
            u32 tag = tag_of(k);
            u64 at = find(k, tag);
            present[j] = at != NO_SLOT;
            if (at == NO_SLOT) continue;
            found++;
            u32 node = table_[at].node;
            unlink(node);
            if (erase) {
                vacate(at);
                release(node);
            } else {
                link_newest(node);
            }
        }
        counters_[HITS] += fabricated ? n : found;
        counters_[MISSES] += fabricated ? 0 : n - found;
        if (erase) counters_[ERASES] += found;
        *n_present = found;
        return (int64_t)live_;
    }

    // Insert keys[idx[0..n)] in that order (idx null: keys[0..n)). A key
    // already present is touched, not counted; past the bound the oldest
    // goes, key by key. Returns the size afterwards.
    int64_t add(const u8* keys, const int64_t* idx, int64_t n,
                int64_t* inserted, int64_t* evicted) {
        std::lock_guard<std::mutex> hold(mu_);
        reserve(live_ + (u64)n);
        int64_t ins = 0, ev = 0;
        for (int64_t j = 0; j < n; j++) {
            const u8* k = keys + 32 * (idx ? idx[j] : j);
            u32 tag = tag_of(k);
            u64 at = find(k, tag);
            if (at != NO_SLOT) {
                u32 node = table_[at].node;
                unlink(node);
                link_newest(node);
                continue;
            }
            u32 node = acquire(k);
            link_newest(node);
            occupy(tag, node);
            ins++;
            while (live_ > cap_) {
                u32 old = oldest_;
                unlink(old);
                vacate(find(slab_[old].key, tag_of(slab_[old].key)));
                release(old);
                ev++;
            }
        }
        counters_[INSERTIONS] += ins;
        counters_[EVICTIONS] += ev;
        *inserted = ins;
        *evicted = ev;
        return (int64_t)live_;
    }

    // Drop one key; counted as an erase when it was there.
    int64_t discard(const u8* k, int32_t* was_present) {
        std::lock_guard<std::mutex> hold(mu_);
        u64 at = find(k, tag_of(k));
        *was_present = at != NO_SLOT;
        if (at != NO_SLOT) {
            u32 node = table_[at].node;
            unlink(node);
            vacate(at);
            release(node);
            counters_[ERASES]++;
        }
        return (int64_t)live_;
    }

    int64_t size() {
        std::lock_guard<std::mutex> hold(mu_);
        return (int64_t)live_;
    }

    // The keys oldest first, as many as `room` holds; returns how many the
    // set has.
    int64_t keys(u8* out, int64_t room) {
        std::lock_guard<std::mutex> hold(mu_);
        int64_t j = 0;
        for (u32 node = oldest_; node != NIL && j < room;
             node = slab_[node].next, j++)
            std::memcpy(out + 32 * j, slab_[node].key, 32);
        return (int64_t)live_;
    }

    // The five counters, of one instant.
    void counters(int64_t* out) {
        std::lock_guard<std::mutex> hold(mu_);
        std::memcpy(out, counters_, sizeof counters_);
    }

  private:
    static constexpr u32 NIL = 0xFFFFFFFFu;
    static constexpr u64 NO_SLOT = ~(u64)0;
    static constexpr u64 MAX_NODES = 0x7FFFFFFFu;

    struct Node {
        u8 key[32];
        u32 prev, next;
    };
    struct Slot {
        u32 tag;
        u32 node;  // NIL: empty
    };

    // The keys are salted SHA-256 digests, but nothing here leans on that:
    // all four words are folded before the multiply, so keys that differ in
    // one byte anywhere still spread.
    static u32 tag_of(const u8* k) {
        u64 w[4];
        std::memcpy(w, k, 32);
        u64 x = w[0] ^ rotl(w[1], 13) ^ rotl(w[2], 29) ^ rotl(w[3], 47);
        return (u32)((x * 0x9E3779B97F4A7C15ull) >> 32);
    }
    static u64 rotl(u64 v, int r) { return (v << r) | (v >> (64 - r)); }

    u64 home(u32 tag) const { return tag >> (32 - bits_); }

    u64 find(const u8* k, u32 tag) const {
        for (u64 at = home(tag);; at = (at + 1) & mask_) {
            const Slot& s = table_[at];
            if (s.node == NIL) return NO_SLOT;
            if (s.tag == tag && std::memcmp(slab_[s.node].key, k, 32) == 0)
                return at;
        }
    }

    void occupy(u32 tag, u32 node) {
        u64 at = home(tag);
        while (table_[at].node != NIL) at = (at + 1) & mask_;
        table_[at] = Slot{tag, node};
    }

    // Empty a slot and close the gap: each follower moves back unless its
    // home lies cyclically after the hole (linear probing without
    // tombstones).
    void vacate(u64 hole) {
        for (u64 at = (hole + 1) & mask_; table_[at].node != NIL;
             at = (at + 1) & mask_) {
            u64 h = home(table_[at].tag);
            if (((at - h) & mask_) >= ((at - hole) & mask_)) {
                table_[hole] = table_[at];
                hole = at;
            }
        }
        table_[hole].node = NIL;
    }

    void rebuild(int bits) {
        // the one allocation comes first: if it throws, nothing has moved
        std::vector<Slot> old((size_t)1 << bits, Slot{0, NIL});
        old.swap(table_);
        bits_ = bits;
        mask_ = ((u64)1 << bits) - 1;
        for (const Slot& s : old)
            if (s.node != NIL) occupy(s.tag, s.node);
    }

    // Room for `want` keys (the bound, and the one over it that an insert
    // holds before it evicts, at most), made before a call's first insert:
    // one rebuild a bulk call, not a doubling at a time, and no allocation
    // once the set is being changed.
    void reserve(u64 want) {
        if (want > cap_ + 1) want = cap_ + 1;
        int bits = bits_;
        while (2 * want > ((u64)1 << bits)) bits++;
        if (bits != bits_) rebuild(bits);
        if (want > slab_.capacity())
            slab_.reserve((size_t)std::min<u64>(
                cap_ + 1, std::max<u64>(want, 2 * slab_.capacity())));
    }

    u32 acquire(const u8* k) {
        u32 node;
        if (free_ != NIL) {
            node = free_;
            free_ = slab_[node].next;
        } else {
            node = (u32)slab_.size();
            slab_.emplace_back();
        }
        std::memcpy(slab_[node].key, k, 32);
        live_++;
        return node;
    }

    void release(u32 node) {
        slab_[node].next = free_;
        free_ = node;
        live_--;
    }

    void unlink(u32 node) {
        Node& nd = slab_[node];
        if (nd.prev != NIL) slab_[nd.prev].next = nd.next; else oldest_ = nd.next;
        if (nd.next != NIL) slab_[nd.next].prev = nd.prev; else newest_ = nd.prev;
    }

    void link_newest(u32 node) {
        Node& nd = slab_[node];
        nd.prev = newest_;
        nd.next = NIL;
        if (newest_ != NIL) slab_[newest_].next = node; else oldest_ = node;
        newest_ = node;
    }

    std::mutex mu_;
    const u64 cap_;
    std::vector<Node> slab_;
    std::vector<Slot> table_;
    int bits_ = 0;
    u64 mask_ = 0;
    u32 oldest_ = NIL, newest_ = NIL, free_ = NIL;
    u64 live_ = 0;
    int64_t counters_[N_COUNTERS] = {0, 0, 0, 0, 0};
};

}  // namespace nat

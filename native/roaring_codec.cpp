// Native roaring codec: the hot host-side decode/encode loops.
//
// Mirrors pilosa_tpu/roaring.py (the Pilosa wire variant of
// roaring.go:1046 WriteTo / :5315 readers). This layer plays the role
// the reference's roaring/ package plays for its runtime: the
// performance-critical host path between wire/disk bytes and the dense
// uint32 blocks uploaded to the TPU.
//
// C ABI (ctypes-friendly), two-phase calls so Python owns allocation:
//   roaring_decode_count(buf, len)              -> bit count or -1
//   roaring_decode(buf, len, out_u64, cap)      -> n written or -1
//   roaring_encode_bound(pos_u64, n)            -> max encoded bytes
//   roaring_encode(pos_u64, n, out_u8, cap)     -> bytes written or -1
//   positions_to_words(pos_u64, n, words_u32, n_words)   (pos < n_words*32)
//   positions_to_rows(mat_u32, n_rows, n_words, src_addr_u64, len_i64,
//                     dst_row_i64, n)           -> 0, or -1 (refused)
//   words_to_positions(words_u32, n_words, out_u64, cap) -> n
//   popcount_words(words_u32, n_words)          -> total set bits

#include <cstdint>
#include <cstdlib>
#include <cstring>
#include <mutex>
#include <vector>

#if defined(__AVX2__)
#include <immintrin.h>
#endif
#if defined(__linux__)
#include <sys/mman.h>
#endif

namespace {

// --- write-combining radix partition -------------------------------------
//
// Shared by the bulk-import scatters: partitioning N random keys into
// ~1000 per-shard output streams is memory-bandwidth bound, and naive
// per-element stores both thrash the TLB (each store lands on a cold
// page of a 100s-of-MB buffer) and pollute the cache with lines that
// are written once and never read back.  Classic fix: stage 16 values
// (one cache line) per shard in an L1-resident buffer and flush full
// lines with non-temporal stores.  Segment starts are padded to
// 16-element alignment so every flush is a whole aligned line.

// Ask the kernel for 2 MiB pages on a large fresh buffer BEFORE first
// touch: on virtualized hosts each 4 KiB first-touch fault costs
// microseconds, so a 200 MB staging buffer pays >1 s in faults alone —
// with huge pages that drops to ~100 faults (and the TLB stops
// thrashing during the many-stream partition writes).
inline void advise_huge(void* p, size_t len) {
#if defined(__linux__) && defined(MADV_HUGEPAGE)
  uintptr_t a = (reinterpret_cast<uintptr_t>(p) + 4095) & ~uintptr_t(4095);
  uintptr_t e = (reinterpret_cast<uintptr_t>(p) + len) & ~uintptr_t(4095);
  if (e > a) madvise(reinterpret_cast<void*>(a), e - a, MADV_HUGEPAGE);
#else
  (void)p;
  (void)len;
#endif
}

void* pool_alloc_impl(int64_t bytes, int zero);
void pool_free_impl(void* p, int64_t bytes);

struct Partitioned {
  // start[s] (inclusive) .. end[s] (exclusive) index shard s's values
  // inside the 64-byte-aligned buffer `part` (a pool staging chunk,
  // returned to the pool on destruction).
  std::vector<int64_t> start, end;
  uint32_t* part = nullptr;
  void* owned = nullptr;
  int64_t owned_bytes = 0;
  ~Partitioned() {
    if (owned != nullptr) pool_free_impl(owned, owned_bytes);
  }
};

// --- recycled page pool ---------------------------------------------------
//
// Buffer pool for the large (100s of MB) block/staging buffers the bulk
// import path churns through. On virtualized hosts without working
// transparent huge pages (AnonHugePages: 0 even under MADV_HUGEPAGE),
// first-touch faults on a fresh anonymous mapping run at ~0.7-2 GB/s —
// slower than the import math itself — while an explicit memset of
// already-faulted memory runs at ~8 GB/s. Classic database answer:
// fault pages once (at boot via pool_reserve, or on first import) and
// recycle them forever. Plays the role the reference's mmapped
// fragment files + page cache play (fragment.go:311 openStorage):
// storage memory there is also faulted once and reused by the kernel.
//
// Best-fit freelist over privately mmapped chunks, 2 MiB granularity,
// split on allocation, never coalesced (the workload is a handful of
// large long-lived block arrays plus per-import staging; external
// fragmentation is bounded in practice and the limit evicts cleanly).
constexpr size_t kPoolAlign = size_t(2) << 20;  // 2 MiB granularity

struct PoolChunk {
  uint8_t* p;
  size_t sz;
};

std::mutex g_pool_mu;
std::vector<PoolChunk> g_pool_free;       // recycled, fault-warm chunks
size_t g_pool_free_bytes = 0;
size_t g_pool_limit = size_t(3) << 30;    // retained-bytes cap (3 GiB)
bool g_pool_limit_explicit = false;       // set via pool_set_limit: an
// operator-stated cap is a hard upper bound — pool_reserve must clamp
// to it, never raise it (ADVICE r4 #4).
int64_t g_pool_fresh_mmaps = 0;           // stats: cold allocations
int64_t g_pool_recycled = 0;              // stats: warm allocations

inline size_t pool_round(size_t bytes) {
  return (bytes + kPoolAlign - 1) & ~(kPoolAlign - 1);
}

// Recycling requires mmap (chunks are split at arbitrary offsets, so a
// freed pointer may be interior to its original mapping — munmap of a
// page range handles that; free() cannot). Off Linux the pool degrades
// to plain calloc/free with no freelist: correct, just not warm.
#if defined(__linux__)
uint8_t* pool_mmap(size_t sz) {
  void* p = mmap(nullptr, sz, PROT_READ | PROT_WRITE,
                 MAP_PRIVATE | MAP_ANONYMOUS, -1, 0);
  if (p == MAP_FAILED) return nullptr;
  advise_huge(p, sz);
  return static_cast<uint8_t*>(p);
}

void pool_munmap(uint8_t* p, size_t sz) { munmap(p, sz); }
#endif

// Evict largest-first while over the retained cap. Caller holds the lock.
#if !defined(__linux__)
void pool_enforce_limit_locked() {}  // freelist never populated off Linux
#else
void pool_enforce_limit_locked() {
  while (g_pool_free_bytes > g_pool_limit && !g_pool_free.empty()) {
    size_t worst = 0;
    for (size_t i = 1; i < g_pool_free.size(); i++)
      if (g_pool_free[i].sz > g_pool_free[worst].sz) worst = i;
    g_pool_free_bytes -= g_pool_free[worst].sz;
    pool_munmap(g_pool_free[worst].p, g_pool_free[worst].sz);
    g_pool_free[worst] = g_pool_free.back();
    g_pool_free.pop_back();
  }
}
#endif

// Allocate `bytes` (rounded to 2 MiB). zero!=0 gives np.zeros semantics;
// recycled chunks are memset (fast: pages already faulted), fresh mmaps
// are kernel-zeroed lazily. Returns nullptr on failure.
void* pool_alloc_impl(int64_t bytes, int zero) {
  if (bytes <= 0) return nullptr;
  size_t need = pool_round(static_cast<size_t>(bytes));
#if !defined(__linux__)
  return zero ? std::calloc(need, 1) : std::malloc(need);
#else
  uint8_t* p = nullptr;
  bool recycled = false;
  {
    std::lock_guard<std::mutex> g(g_pool_mu);
    size_t best = g_pool_free.size();
    for (size_t i = 0; i < g_pool_free.size(); i++)
      if (g_pool_free[i].sz >= need &&
          (best == g_pool_free.size() ||
           g_pool_free[i].sz < g_pool_free[best].sz))
        best = i;
    if (best < g_pool_free.size()) {
      PoolChunk c = g_pool_free[best];
      g_pool_free[best] = g_pool_free.back();
      g_pool_free.pop_back();
      g_pool_free_bytes -= c.sz;
      if (c.sz > need) {  // split: tail goes back on the freelist
        g_pool_free.push_back({c.p + need, c.sz - need});
        g_pool_free_bytes += c.sz - need;
      }
      p = c.p;
      recycled = true;
      g_pool_recycled++;
    }
  }
  if (p == nullptr) {
    p = pool_mmap(need);
    if (p == nullptr) return nullptr;
    std::lock_guard<std::mutex> g(g_pool_mu);
    g_pool_fresh_mmaps++;
  }
  if (zero && recycled) std::memset(p, 0, need);
  return p;
#endif
}

void pool_free_impl(void* p, int64_t bytes) {
  if (p == nullptr || bytes <= 0) return;
#if !defined(__linux__)
  std::free(p);
#else
  size_t sz = pool_round(static_cast<size_t>(bytes));
  std::lock_guard<std::mutex> g(g_pool_mu);
  g_pool_free.push_back({static_cast<uint8_t*>(p), sz});
  g_pool_free_bytes += sz;
  pool_enforce_limit_locked();
#endif
}

inline void flush_line(uint32_t* dst, const uint32_t* src) {
#if defined(__AVX2__)
  _mm256_stream_si256(reinterpret_cast<__m256i*>(dst),
                      _mm256_load_si256(reinterpret_cast<const __m256i*>(src)));
  _mm256_stream_si256(reinterpret_cast<__m256i*>(dst) + 1,
                      _mm256_load_si256(reinterpret_cast<const __m256i*>(src) + 1));
#else
  std::memcpy(dst, src, 64);
#endif
}

// Partition local positions (cols & mask) by shard (cols >> exp).
// Returns false on allocation failure.  Out-of-range shards are dropped,
// matching the historical scatter behaviour.
bool partition_by_shard(const uint64_t* cols, int64_t n, int exp,
                        int64_t n_shards, Partitioned& out) {
  const uint64_t mask = (1ULL << exp) - 1;
  std::vector<int64_t> count(n_shards, 0);
  for (int64_t k = 0; k < n; k++) {
    uint64_t s = cols[k] >> exp;
    if (static_cast<int64_t>(s) < n_shards) count[s]++;
  }
  out.start.resize(n_shards + 1);
  out.start[0] = 0;
  for (int64_t s = 0; s < n_shards; s++)
    out.start[s + 1] = out.start[s] + ((count[s] + 15) & ~15LL);
  const size_t part_bytes = ((out.start[n_shards] + 15) & ~15LL) * 4 + 64;
  out.owned = pool_alloc_impl(static_cast<int64_t>(part_bytes), 0);
  if (out.owned == nullptr) return false;
  out.owned_bytes = static_cast<int64_t>(part_bytes);
  out.part = reinterpret_cast<uint32_t*>(
      (reinterpret_cast<uintptr_t>(out.owned) + 63) & ~uintptr_t(63));
  std::vector<int64_t> head(out.start.begin(), out.start.end() - 1);
  std::vector<uint32_t> stage(n_shards * 16 + 16);
  uint32_t* stg = reinterpret_cast<uint32_t*>(
      (reinterpret_cast<uintptr_t>(stage.data()) + 63) & ~uintptr_t(63));
  std::vector<uint8_t> fill(n_shards, 0);
  for (int64_t k = 0; k < n; k++) {
    uint64_t c = cols[k];
    uint64_t s = c >> exp;
    if (static_cast<int64_t>(s) >= n_shards) continue;
    uint8_t f = fill[s];
    stg[s * 16 + f] = static_cast<uint32_t>(c & mask);
    if (++f == 16) {
      flush_line(&out.part[head[s]], &stg[s * 16]);
      head[s] += 16;
      f = 0;
    }
    fill[s] = f;
  }
#if defined(__AVX2__)
  _mm_sfence();
#endif
  for (int64_t s = 0; s < n_shards; s++)
    for (uint8_t i = 0; i < fill[s]; i++)
      out.part[head[s]++] = stg[s * 16 + i];
  out.end.assign(head.begin(), head.end());
  return true;
}

constexpr uint32_t kMagic = 12348;
// Official RoaringFormatSpec cookies (32-bit roaring; the constants are
// the public interchange format, reference roaring.go:5310-5313).
constexpr uint32_t kOfficialNoRuns = 12346;
constexpr uint32_t kOfficialRuns = 12347;
constexpr int kTypeArray = 1;
constexpr int kTypeBitmap = 2;
constexpr int kTypeRun = 3;
//: internal: official-spec run container — runs are (start, LENGTH)
//: pairs, unlike the pilosa variant's (start, last).
constexpr int kTypeRunOfficial = 4;
constexpr int kArrayMax = 4096;
constexpr int kRunMax = 2048;
constexpr int kBitmapWords64 = (1 << 16) / 64;

inline uint16_t rd16(const uint8_t* p) {
  return static_cast<uint16_t>(p[0] | (p[1] << 8));
}
inline uint32_t rd32(const uint8_t* p) {
  return static_cast<uint32_t>(p[0]) | (static_cast<uint32_t>(p[1]) << 8) |
         (static_cast<uint32_t>(p[2]) << 16) |
         (static_cast<uint32_t>(p[3]) << 24);
}
inline uint64_t rd64(const uint8_t* p) {
  uint64_t v = 0;
  std::memcpy(&v, p, 8);  // little-endian hosts only (x86/arm LE)
  return v;
}
inline void wr16(uint8_t* p, uint16_t v) {
  p[0] = v & 0xFF;
  p[1] = v >> 8;
}
inline void wr32(uint8_t* p, uint32_t v) {
  p[0] = v & 0xFF;
  p[1] = (v >> 8) & 0xFF;
  p[2] = (v >> 16) & 0xFF;
  p[3] = (v >> 24) & 0xFF;
}
inline void wr64(uint8_t* p, uint64_t v) { std::memcpy(p, &v, 8); }

struct Meta {
  uint64_t key;
  int typ;
  int n;
  uint32_t off;
};

// Official RoaringFormatSpec header+metas (readOfficialHeader behavior,
// roaring.go:5316-5374): u16 keys, cardinality-based container typing,
// run bitmap with cookie 12347, offset header present unless
// (runs && size < 4) — then containers are laid out sequentially.
int parse_official(const uint8_t* buf, int64_t len,
                   std::vector<Meta>* metas) {
  if (len < 8) return -1;
  uint32_t cookie = rd32(buf);
  uint32_t size;
  int64_t pos = 4;
  const uint8_t* run_bitmap = nullptr;
  bool have_runs = false;
  if (cookie == kOfficialNoRuns) {
    size = rd32(buf + 4);
    pos = 8;
  } else if ((cookie & 0xFFFF) == kOfficialRuns) {
    have_runs = true;
    size = (cookie >> 16) + 1;
    int64_t rb = (static_cast<int64_t>(size) + 7) / 8;
    if (pos + rb > len) return -1;
    run_bitmap = buf + pos;
    pos += rb;
  } else {
    return -1;
  }
  if (size > (1u << 16)) return -1;
  int64_t hdr = pos;
  if (pos + 4LL * size > len) return -1;
  pos += 4LL * size;
  bool have_offsets = !have_runs || size >= 4;
  const uint8_t* offsets = nullptr;
  if (have_offsets) {
    if (pos + 4LL * size > len) return -1;
    offsets = buf + pos;
    pos += 4LL * size;
    // Containers are sequential and non-overlapping; aliased or
    // decreasing offsets let a tiny buffer emit unbounded data.
    uint32_t prev = 0;
    for (uint32_t i = 0; i < size; i++) {
      uint32_t o = rd32(offsets + 4LL * i);
      if (o < pos || (i > 0 && o <= prev)) return -1;
      prev = o;
    }
  }
  int64_t data_off = pos;
  metas->resize(size);
  for (uint32_t i = 0; i < size; i++) {
    Meta& m = (*metas)[i];
    m.key = rd16(buf + hdr + 4LL * i);
    m.n = rd16(buf + hdr + 4LL * i + 2) + 1;
    bool is_run = run_bitmap && ((run_bitmap[i / 8] >> (i % 8)) & 1);
    // <=: official writers keep arrays up to EXACTLY 4096 values (the
    // reference's `card < ArrayMaxSize` typer misreads those; 4096 u16s
    // happen to be one bitmap's 8192 bytes, so nothing bounds-checks).
    m.typ = is_run ? kTypeRunOfficial
                   : (m.n <= kArrayMax ? kTypeArray : kTypeBitmap);
    if (offsets) {
      m.off = rd32(offsets + 4LL * i);
    } else {
      if (data_off > len || data_off > UINT32_MAX) return -1;
      m.off = static_cast<uint32_t>(data_off);
      switch (m.typ) {  // sequential layout: advance past this container
        case kTypeArray:
          data_off += 2LL * m.n;
          break;
        case kTypeBitmap:
          data_off += 8LL * kBitmapWords64;
          break;
        case kTypeRunOfficial: {
          if (data_off + 2 > len) return -1;
          int rc = rd16(buf + data_off);
          data_off += 2 + 4LL * rc;
          break;
        }
      }
    }
  }
  return static_cast<int>(size);
}

// Parse header + metas; returns container count or -1. Dispatches on
// the cookie: pilosa variant (12348) or official spec (12346/12347).
int parse_metas(const uint8_t* buf, int64_t len, std::vector<Meta>* metas) {
  if (len < 8) return -1;
  uint32_t cookie = rd32(buf);
  if ((cookie & 0xFFFF) != kMagic) return parse_official(buf, len, metas);
  int count = static_cast<int>(rd32(buf + 4));
  int64_t meta_off = 8;
  int64_t offs_off = meta_off + 12LL * count;
  if (count < 0 || offs_off + 4LL * count > len) return -1;
  metas->resize(count);
  for (int i = 0; i < count; i++) {
    const uint8_t* m = buf + meta_off + 12LL * i;
    (*metas)[i].key = rd64(m);
    (*metas)[i].typ = rd16(m + 8);
    (*metas)[i].n = rd16(m + 10) + 1;
    (*metas)[i].off = rd32(buf + offs_off + 4LL * i);
  }
  return count;
}

}  // namespace

extern "C" {

// --- pool C ABI (see "recycled page pool" above) --------------------------

void* pool_alloc(int64_t bytes, int zero) { return pool_alloc_impl(bytes, zero); }

void pool_free(void* p, int64_t bytes) { pool_free_impl(p, bytes); }

// Pre-fault `bytes` of pool memory (server boot / before a bulk load).
// Returns bytes actually reserved (0 on failure).
int64_t pool_reserve(int64_t bytes) {
#if !defined(__linux__)
  (void)bytes;
  return 0;  // no freelist off Linux — nothing to pre-fault
#else
  if (bytes <= 0) return 0;
  size_t sz = pool_round(static_cast<size_t>(bytes));
  {
    // Size the reserve under the lock BEFORE faulting pages: with an
    // operator-set cap (pool_set_limit) the cap is a hard bound — we
    // clamp the reserve to the remaining headroom instead of raising
    // the cap, and report the clamped size so the caller's top-up loop
    // sees the truth.
    std::lock_guard<std::mutex> g(g_pool_mu);
    if (g_pool_limit_explicit) {
      size_t headroom = g_pool_limit > g_pool_free_bytes
                            ? g_pool_limit - g_pool_free_bytes : 0;
      headroom &= ~(kPoolAlign - 1);
      if (headroom == 0) return 0;
      if (sz > headroom) sz = headroom;
    }
  }
  uint8_t* p = pool_mmap(sz);
  if (p == nullptr) return 0;
  std::memset(p, 0, sz);  // fault every page now, off the import path
  std::lock_guard<std::mutex> g(g_pool_mu);
  if (!g_pool_limit_explicit) {
    // Without an operator cap, a reserve states intent and may grow
    // the default cap to cover itself — but only now that the chunk
    // exists (growing before a failed mmap would permanently inflate
    // the cap with nothing to show for it).
    if (g_pool_limit < g_pool_free_bytes + sz)
      g_pool_limit = g_pool_free_bytes + sz;
  } else if (g_pool_free_bytes + sz > g_pool_limit) {
    // Headroom moved between the clamp and here (a concurrent
    // pool_free refilled the pool): re-clamp by trimming the tail of
    // the chunk we just faulted, so the return value never overstates
    // what the pool retained.
    size_t keep = g_pool_limit > g_pool_free_bytes
                      ? (g_pool_limit - g_pool_free_bytes)
                            & ~(kPoolAlign - 1)
                      : 0;
    if (keep == 0) {
      pool_munmap(p, sz);
      return 0;
    }
    pool_munmap(p + keep, sz - keep);
    sz = keep;
  }
  g_pool_free.push_back({p, sz});
  g_pool_free_bytes += sz;
  g_pool_fresh_mmaps++;
  pool_enforce_limit_locked();
  return static_cast<int64_t>(sz);
#endif
}

void pool_set_limit(int64_t bytes) {
  std::lock_guard<std::mutex> g(g_pool_mu);
  g_pool_limit = bytes < 0 ? 0 : static_cast<size_t>(bytes);
  g_pool_limit_explicit = true;
  pool_enforce_limit_locked();
}

// out[0]=free_bytes out[1]=fresh_mmaps out[2]=recycled_allocs out[3]=limit
void pool_stats(int64_t* out) {
  std::lock_guard<std::mutex> g(g_pool_mu);
  out[0] = static_cast<int64_t>(g_pool_free_bytes);
  out[1] = g_pool_fresh_mmaps;
  out[2] = g_pool_recycled;
  out[3] = static_cast<int64_t>(g_pool_limit);
}

int64_t roaring_decode_count(const uint8_t* buf, int64_t len) {
  std::vector<Meta> metas;
  if (parse_metas(buf, len, &metas) < 0) return -1;
  int64_t total = 0;
  for (const Meta& m : metas) total += m.n;
  // Allocation-DoS guard: a 4-byte run can legitimately encode 65536
  // values, so len*16384 bounds any honest buffer; claims beyond it are
  // adversarial (the caller allocates `total` uint64s).
  if (total > len * 16384 + 65536) return -1;
  return total;
}

int64_t roaring_decode(const uint8_t* buf, int64_t len, uint64_t* out,
                       int64_t cap) {
  std::vector<Meta> metas;
  if (parse_metas(buf, len, &metas) < 0) return -1;
  int64_t n_out = 0;
  for (const Meta& m : metas) {
    uint64_t base = m.key << 16;
    const uint8_t* data = buf + m.off;
    // cap guards below use the ACTUAL content (popcounts, run
    // lengths), never the claimed N: an adversarial buffer can claim
    // N=1 while a run/bitmap emits 65536 values — trusting N was a
    // heap overflow (caller allocates from roaring_decode_count).
    switch (m.typ) {
      case kTypeArray: {
        if (m.off + 2LL * m.n > len) return -1;
        if (n_out + m.n > cap) return -1;
        for (int i = 0; i < m.n; i++) out[n_out++] = base + rd16(data + 2 * i);
        break;
      }
      case kTypeBitmap: {
        if (m.off + 8LL * kBitmapWords64 > len) return -1;
        for (int w = 0; w < kBitmapWords64; w++) {
          uint64_t word = rd64(data + 8 * w);
          if (word && n_out + __builtin_popcountll(word) > cap) return -1;
          while (word) {
            int b = __builtin_ctzll(word);
            out[n_out++] = base + (static_cast<uint64_t>(w) << 6) + b;
            word &= word - 1;
          }
        }
        break;
      }
      case kTypeRun:
      case kTypeRunOfficial: {
        if (m.off + 2 > len) return -1;
        int run_n = rd16(data);
        if (m.off + 2 + 4LL * run_n > len) return -1;
        for (int r = 0; r < run_n; r++) {
          uint16_t start = rd16(data + 2 + 4 * r);
          uint32_t last = rd16(data + 2 + 4 * r + 2);
          if (m.typ == kTypeRunOfficial) {
            // Official spec stores (start, length): last = start + len
            // (officialRoaringIterator conversion, roaring.go:1404).
            last += start;
            if (last > 0xFFFF) return -1;
          }
          if (last >= start &&
              n_out + (static_cast<int64_t>(last) - start + 1) > cap)
            return -1;
          for (uint32_t v = start; v <= last; v++) out[n_out++] = base + v;
        }
        break;
      }
      default:
        return -1;
    }
  }
  return n_out;
}

int64_t roaring_encode_bound(const uint64_t* pos, int64_t n) {
  (void)pos;
  // Worst case: every position its own array container.
  return 8 + n * (12 + 4 + 2) + 16;
}

int64_t roaring_encode(const uint64_t* pos, int64_t n, uint8_t* out,
                       int64_t cap) {
  // PRECONDITION: pos is strictly increasing (unique-sorted); the Python
  // binding (pilosa_tpu/native/__init__.py encode_roaring) enforces it.
  // Group sorted positions by 2^16 key; pick run/array/bitmap per the
  // reference's optimize() economics (roaring.go:2334).
  struct Cont {
    uint64_t key;
    int typ;
    int n;
    int64_t start;  // index into pos
  };
  std::vector<Cont> conts;
  int64_t i = 0;
  while (i < n) {
    uint64_t key = pos[i] >> 16;
    int64_t j = i;
    int runs = 1;
    while (j + 1 < n && (pos[j + 1] >> 16) == key) {
      if (pos[j + 1] != pos[j] + 1) runs++;
      j++;
    }
    int cn = static_cast<int>(j - i + 1);
    int run_size = 2 + 4 * runs;
    int array_size = 2 * cn;
    int typ;
    if (runs <= kRunMax && run_size < array_size && run_size < 8192)
      typ = kTypeRun;
    else if (cn <= kArrayMax)
      typ = kTypeArray;
    else
      typ = kTypeBitmap;
    conts.push_back({key, typ, cn, i});
    i = j + 1;
  }
  int count = static_cast<int>(conts.size());
  int64_t head = 8 + 12LL * count + 4LL * count;
  if (head > cap) return -1;
  wr32(out, kMagic);
  wr32(out + 4, static_cast<uint32_t>(count));
  int64_t off = head;
  for (int c = 0; c < count; c++) {
    const Cont& ct = conts[c];
    uint8_t* m = out + 8 + 12LL * c;
    wr64(m, ct.key);
    wr16(m + 8, static_cast<uint16_t>(ct.typ));
    wr16(m + 10, static_cast<uint16_t>(ct.n - 1));
    wr32(out + 8 + 12LL * count + 4LL * c, static_cast<uint32_t>(off));
    // payload
    const uint64_t* p = pos + ct.start;
    if (ct.typ == kTypeArray) {
      if (off + 2LL * ct.n > cap) return -1;
      for (int k = 0; k < ct.n; k++)
        wr16(out + off + 2LL * k, static_cast<uint16_t>(p[k] & 0xFFFF));
      off += 2LL * ct.n;
    } else if (ct.typ == kTypeRun) {
      // recount runs
      std::vector<std::pair<uint16_t, uint16_t>> runs;
      uint16_t start = static_cast<uint16_t>(p[0] & 0xFFFF);
      uint16_t prev = start;
      for (int k = 1; k < ct.n; k++) {
        uint16_t v = static_cast<uint16_t>(p[k] & 0xFFFF);
        if (v != prev + 1) {
          runs.emplace_back(start, prev);
          start = v;
        }
        prev = v;
      }
      runs.emplace_back(start, prev);
      int64_t sz = 2 + 4LL * runs.size();
      if (off + sz > cap) return -1;
      wr16(out + off, static_cast<uint16_t>(runs.size()));
      for (size_t r = 0; r < runs.size(); r++) {
        wr16(out + off + 2 + 4 * r, runs[r].first);
        wr16(out + off + 2 + 4 * r + 2, runs[r].second);
      }
      off += sz;
    } else {
      int64_t sz = 8LL * kBitmapWords64;
      if (off + sz > cap) return -1;
      std::memset(out + off, 0, sz);
      for (int k = 0; k < ct.n; k++) {
        uint16_t v = static_cast<uint16_t>(p[k] & 0xFFFF);
        out[off + (v >> 3)] |= static_cast<uint8_t>(1u << (v & 7));
      }
      off += sz;
    }
  }
  return off;
}

void positions_to_words(const uint64_t* pos, int64_t n, uint32_t* words,
                        int64_t n_words) {
  for (int64_t k = 0; k < n; k++) {
    uint64_t p = pos[k];
    int64_t w = static_cast<int64_t>(p >> 5);
    if (w < n_words) words[w] |= 1u << (p & 31);
  }
}

// One call a row stack (MeshPlanner._build_stack): OR the bit positions
// of `n` source arrays into rows of one [n_rows, n_words] matrix.
// src[k] is the address of len[k] uint64 positions, dst[k] the matrix
// row they go to. The caller holds a reference to every source for the
// whole call and holds no interpreter lock: 954 rows cost one hand-over
// of it, not 954. A position at or past the row width is ignored, as
// positions_to_words ignores it. Returns -1, with nothing written, for
// a destination row outside the matrix or a negative length.
int positions_to_rows(uint32_t* mat, int64_t n_rows, int64_t n_words,
                      const uint64_t* src, const int64_t* len,
                      const int64_t* dst, int64_t n) {
  if (n_rows < 0 || n_words < 0 || n < 0) return -1;
  for (int64_t k = 0; k < n; k++)
    if (dst[k] < 0 || dst[k] >= n_rows || len[k] < 0) return -1;
  for (int64_t k = 0; k < n; k++)
    positions_to_words(
        reinterpret_cast<const uint64_t*>(static_cast<uintptr_t>(src[k])),
        len[k], mat + dst[k] * n_words, n_words);
  return 0;
}

int64_t words_to_positions(const uint32_t* words, int64_t n_words,
                           uint64_t* out, int64_t cap) {
  int64_t n = 0;
  for (int64_t w = 0; w < n_words; w++) {
    uint32_t word = words[w];
    while (word) {
      int b = __builtin_ctz(word);
      if (n >= cap) return -1;
      out[n++] = (static_cast<uint64_t>(w) << 5) + b;
      word &= word - 1;
    }
  }
  return n;
}

int64_t popcount_words(const uint32_t* words, int64_t n_words) {
  int64_t total = 0;
  for (int64_t w = 0; w < n_words; w++)
    total += __builtin_popcount(words[w]);
  return total;
}

int64_t intersection_count_words(const uint32_t* a, const uint32_t* b,
                                 int64_t n_words) {
  // Fused popcount(a & b): the CPU-baseline analog of the reference's
  // intersectionCountBitmapBitmap (roaring.go:3121) — POPCNT over the
  // word stream, autovectorized at -O3 -march=native. ctypes releases
  // the GIL around this call, so per-shard threads scale like the
  // reference's goroutine worker pool.
  int64_t total = 0;
  for (int64_t w = 0; w < n_words; w++)
    total += __builtin_popcount(a[w] & b[w]);
  return total;
}

void scatter_row_blocks(const uint64_t* cols, int64_t n, int exp,
                        uint32_t* blocks, int64_t n_shards,
                        int64_t words_per_shard, uint8_t* touched,
                        int64_t* block_counts) {
  // Bulk-import scatter for ONE bitmap row: absolute column ids ->
  // dense per-shard word blocks (blocks is [n_shards, words_per_shard],
  // caller-zeroed). The order-insensitivity of a bitset means no sort
  // is needed — this is what lets the import path hit memory-bandwidth
  // rates where the reference walks roaring containers per bit batch
  // (fragment.go:1997 -> AddN).
  //
  // Two-phase for cache locality: a direct scatter across all blocks
  // misses cache on every bit (the block array spans 100s of MB), so
  // first radix-PARTITION the local positions by shard — the ~n_shards
  // sequential write heads stay cache-resident — then set bits shard by
  // shard into one block that fits in L2.
  const uint64_t mask = (1ULL << exp) - 1;
  // Small batches: partitioning overhead isn't worth it.
  Partitioned p;
  if (n < (1 << 18) || n_shards <= 4 ||
      !partition_by_shard(cols, n, exp, n_shards, p)) {
    for (int64_t k = 0; k < n; k++) {
      uint64_t c = cols[k];
      uint64_t shard = c >> exp;
      if (static_cast<int64_t>(shard) >= n_shards) continue;
      uint64_t local = c & mask;
      blocks[shard * words_per_shard + (local >> 5)] |= 1u << (local & 31);
      touched[shard] = 1;
    }
    if (block_counts != nullptr)
      for (int64_t s = 0; s < n_shards; s++) {
        if (!touched[s]) continue;
        const uint32_t* block = blocks + s * words_per_shard;
        int64_t total = 0;
        for (int64_t w = 0; w < words_per_shard; w++)
          total += __builtin_popcount(block[w]);
        block_counts[s] = total;
      }
    return;
  }
  for (int64_t s = 0; s < n_shards; s++) {
    int64_t lo = p.start[s], hi = p.end[s];
    if (lo == hi) continue;
    uint32_t* block = blocks + s * words_per_shard;
    // Count fresh bits inline (the old word is already loaded for the
    // OR) — cheaper than a whole-block popcount pass afterwards, which
    // would re-read every word including the untouched majority.
    int64_t cnt = 0;
    for (int64_t k = lo; k < hi; k++) {
      uint32_t local = p.part[k];
      uint32_t bit = 1u << (local & 31);
      uint32_t old = block[local >> 5];
      cnt += (old & bit) == 0;
      block[local >> 5] = old | bit;
    }
    touched[s] = 1;
    if (block_counts != nullptr) block_counts[s] = cnt;
  }
}

int scatter_bsi_blocks(const uint64_t* cols, const int64_t* vals, int64_t n,
                       int exp, int depth, uint32_t* blocks,
                       int64_t n_shards, int64_t words_per_shard,
                       uint8_t* touched, int64_t* block_counts) {
  // BSI bulk-import scatter: (column, value) pairs -> dense bit-plane
  // blocks. blocks is [n_shards, depth+2, words_per_shard] caller-zeroed;
  // per shard the row order is exists, sign, then magnitude planes
  // (fragment BSI layout, reference fragment.go:87-93 + importValue
  // :2205). Shard-partitions first so one shard's whole plane set
  // (~(depth+2) * 128 KiB) stays cache-resident while its bits land.
  // Duplicated columns follow last-write-wins like sequential writes:
  // the exists plane doubles as the batch's seen-set (caller guarantees
  // a FRESH view), so a duplicate clears the column across all planes
  // before the new value lands — no host-side dedupe sort needed.
  const uint64_t mask = (1ULL << exp) - 1;
  const int64_t rows = depth + 2;
  std::vector<int64_t> count(n_shards, 0);
  for (int64_t k = 0; k < n; k++) {
    uint64_t shard = cols[k] >> exp;
    if (static_cast<int64_t>(shard) < n_shards) count[shard]++;
  }
  // Same write-combining partition as scatter_row_blocks, with a
  // parallel int64 value stream (16 values = two 64-byte lines).
  std::vector<int64_t> start(n_shards + 1);
  start[0] = 0;
  for (int64_t s = 0; s < n_shards; s++)
    start[s + 1] = start[s] + ((count[s] + 15) & ~15LL);
  const int64_t cap = start[n_shards];
  const size_t plocal_bytes = ((cap + 15) & ~15LL) * 4 + 64;
  const size_t pval_bytes = ((cap + 15) & ~15LL) * 8 + 128;
  void* plocal_owned = pool_alloc_impl(static_cast<int64_t>(plocal_bytes), 0);
  void* pval_owned = pool_alloc_impl(static_cast<int64_t>(pval_bytes), 0);
  uint32_t* plocal = reinterpret_cast<uint32_t*>(
      (reinterpret_cast<uintptr_t>(plocal_owned) + 63) & ~uintptr_t(63));
  int64_t* pval = reinterpret_cast<int64_t*>(
      (reinterpret_cast<uintptr_t>(pval_owned) + 63) & ~uintptr_t(63));
  struct StagingGuard {
    void *a, *b;
    int64_t an, bn;
    ~StagingGuard() {
      if (a != nullptr) pool_free_impl(a, an);
      if (b != nullptr) pool_free_impl(b, bn);
    }
  } guard{plocal_owned, pval_owned, static_cast<int64_t>(plocal_bytes),
          static_cast<int64_t>(pval_bytes)};
  std::vector<int64_t> head(start.begin(), start.end() - 1);
  std::vector<uint32_t> lstage_v(n_shards * 16 + 16);
  std::vector<int64_t> vstage_v(n_shards * 16 + 8);
  uint32_t* lstage = reinterpret_cast<uint32_t*>(
      (reinterpret_cast<uintptr_t>(lstage_v.data()) + 63) & ~uintptr_t(63));
  int64_t* vstage = reinterpret_cast<int64_t*>(
      (reinterpret_cast<uintptr_t>(vstage_v.data()) + 63) & ~uintptr_t(63));
  std::vector<uint8_t> fill(n_shards, 0);
  if (plocal_owned == nullptr || pval_owned == nullptr) {
    return -1;  // alloc failure: caller must fall back (blocks untouched)
  }
  for (int64_t k = 0; k < n; k++) {
    uint64_t c = cols[k];
    uint64_t shard = c >> exp;
    if (static_cast<int64_t>(shard) >= n_shards) continue;
    uint8_t f = fill[shard];
    lstage[shard * 16 + f] = static_cast<uint32_t>(c & mask);
    vstage[shard * 16 + f] = vals[k];
    if (++f == 16) {
      flush_line(&plocal[head[shard]], &lstage[shard * 16]);
#if defined(__AVX2__)
      for (int i = 0; i < 4; i++)
        _mm256_stream_si256(
            reinterpret_cast<__m256i*>(&pval[head[shard]]) + i,
            _mm256_load_si256(
                reinterpret_cast<const __m256i*>(&vstage[shard * 16]) + i));
#else
      std::memcpy(&pval[head[shard]], &vstage[shard * 16], 128);
#endif
      head[shard] += 16;
      f = 0;
    }
    fill[shard] = f;
  }
#if defined(__AVX2__)
  _mm_sfence();
#endif
  for (int64_t s = 0; s < n_shards; s++)
    for (uint8_t i = 0; i < fill[s]; i++) {
      plocal[head[s]] = lstage[s * 16 + i];
      pval[head[s]++] = vstage[s * 16 + i];
    }
  // Value-at-a-time per shard with INLINE per-plane counts: dedupe
  // first against the exists plane (walking the shard's slice BACKWARD
  // keeps the LAST occurrence, preserving last-write-wins on the
  // caller-guaranteed fresh view), so the set passes never need the
  // all-plane duplicate clear, and counts come for free with the sets —
  // a whole-plane popcount pass afterwards would re-read
  // (depth+2)*128 KiB per shard, dwarfing a sparse batch.
  std::vector<int64_t> cnt(rows);
  for (int64_t s = 0; s < n_shards; s++) {
    int64_t lo = start[s], hi = head[s];
    if (lo == hi) continue;
    uint32_t* base = blocks + s * rows * words_per_shard;
    std::fill(cnt.begin(), cnt.end(), 0);
    for (int64_t k = hi - 1; k >= lo; k--) {
      // Each value touches ~popcount(v) plane words that all share ONE
      // word offset w but sit 128 KiB apart — every touch is a cache
      // miss. The addresses are computable from (plocal, pval) alone,
      // so prefetch a few values ahead: exists + sign + the magnitude's
      // set-bit planes.
      if (k - 4 >= lo) {
        uint32_t pl = plocal[k - 4];
        int64_t pw = pl >> 5;
        __builtin_prefetch(&base[pw], 1);
        int64_t pv = pval[k - 4];
        uint64_t pm;
        if (pv < 0) {
          __builtin_prefetch(&base[words_per_shard + pw], 1);
          pm = static_cast<uint64_t>(-pv);
        } else {
          pm = static_cast<uint64_t>(pv);
        }
        while (pm) {
          int i = __builtin_ctzll(pm);
          pm &= pm - 1;
          if (i < depth)
            __builtin_prefetch(&base[(2 + i) * words_per_shard + pw], 1);
        }
      }
      uint32_t local = plocal[k];
      int64_t w = local >> 5;
      uint32_t bit = 1u << (local & 31);
      if (base[w] & bit) continue;  // a later write owns this column
      base[w] |= bit;  // exists row
      cnt[0]++;
      int64_t v = pval[k];
      uint64_t mag;
      if (v < 0) {
        base[words_per_shard + w] |= bit;  // sign row
        cnt[1]++;
        mag = static_cast<uint64_t>(-v);
      } else {
        mag = static_cast<uint64_t>(v);
      }
      while (mag) {
        int i = __builtin_ctzll(mag);
        mag &= mag - 1;
        if (i < depth) {
          base[(2 + i) * words_per_shard + w] |= bit;
          cnt[2 + i]++;
        }
      }
    }
    touched[s] = 1;
    if (block_counts != nullptr)
      for (int64_t r = 0; r < rows; r++) block_counts[s * rows + r] = cnt[r];
  }
  return 0;
}

}  // extern "C"

// Fuzz harness for the roaring wire codec (pilosa variant + official
// RoaringFormatSpec). Built with ASan/UBSan (`make -C native fuzz`) and
// run in CI via tests/test_roaring_fuzz.py; the full 1e5-iteration run
// is `./fuzz_roaring 100000`.
//
// Strategy (the reference's go-fuzz harness for UnmarshalBinary,
// roaring/fuzzer.go, rebuilt as a self-contained deterministic loop):
//   1. build VALID buffers of all three container types in both formats
//      from a seeded RNG,
//   2. mutate them (byte flips, truncations, splices, length-field
//      tweaks), and
//   3. feed them to roaring_decode_count/roaring_decode, asserting only
//      memory-safety invariants (no OOB — sanitizers — and the output
//      never exceeds the promised capacity).

#include <cstdint>
#include <cstdio>
#include <algorithm>
#include <cstdlib>
#include <cstring>
#include <vector>

extern "C" {
int64_t roaring_decode_count(const uint8_t* buf, int64_t len);
int64_t roaring_decode(const uint8_t* buf, int64_t len, uint64_t* out,
                       int64_t cap);
int64_t roaring_encode_bound(const uint64_t* pos, int64_t n);
int64_t roaring_encode(const uint64_t* pos, int64_t n, uint8_t* out,
                       int64_t cap);
void scatter_row_blocks(const uint64_t* cols, int64_t n, int exp,
                        uint32_t* blocks, int64_t n_shards,
                        int64_t words_per_shard, uint8_t* touched,
                        int64_t* block_counts);
int positions_to_rows(uint32_t* mat, int64_t n_rows, int64_t n_words,
                      const uint64_t* src, const int64_t* len,
                      const int64_t* dst, int64_t n);
int scatter_bsi_blocks(const uint64_t* cols, const int64_t* vals,
                       int64_t n, int exp, int depth, uint32_t* blocks,
                       int64_t n_shards, int64_t words_per_shard,
                       uint8_t* touched, int64_t* block_counts);
}

namespace {

uint64_t rng_state = 0x9E3779B97F4A7C15ull;
uint64_t rnd() {  // xorshift64*
  uint64_t x = rng_state;
  x ^= x >> 12;
  x ^= x << 25;
  x ^= x >> 27;
  rng_state = x;
  return x * 0x2545F4914F6CDD1Dull;
}

void wr16v(std::vector<uint8_t>* b, uint16_t v) {
  b->push_back(v & 0xFF);
  b->push_back(v >> 8);
}
void wr32v(std::vector<uint8_t>* b, uint32_t v) {
  wr16v(b, v & 0xFFFF);
  wr16v(b, v >> 16);
}

// A valid pilosa-variant buffer via the real encoder.
std::vector<uint8_t> seed_pilosa() {
  int n = 1 + rnd() % 2048;
  std::vector<uint64_t> pos(n);
  uint64_t cur = rnd() % 512;
  for (int i = 0; i < n; i++) {
    cur += 1 + rnd() % ((rnd() % 7 == 0) ? 70000 : 3);
    pos[i] = cur;
  }
  int64_t cap = roaring_encode_bound(pos.data(), n);
  std::vector<uint8_t> out(cap);
  int64_t sz = roaring_encode(pos.data(), n, out.data(), cap);
  if (sz < 0) abort();  // encoder must handle its own output
  out.resize(sz);
  return out;
}

// A valid official-spec buffer, hand-assembled (array/bitmap/run mix).
std::vector<uint8_t> seed_official() {
  int n_cont = 1 + rnd() % 5;
  bool with_runs = rnd() & 1;
  std::vector<uint8_t> run_flags((n_cont + 7) / 8, 0);
  struct C {
    uint16_t key;
    int type;  // 0 array, 1 bitmap, 2 run
    std::vector<uint8_t> payload;
    int card;
  };
  std::vector<C> cs(n_cont);
  for (int i = 0; i < n_cont; i++) {
    cs[i].key = i * (1 + rnd() % 3);
    int t = with_runs ? rnd() % 3 : rnd() % 2;
    cs[i].type = t;
    if (t == 0) {  // array
      int card = 1 + rnd() % 1024;
      cs[i].card = card;
      uint16_t v = rnd() % 64;
      for (int k = 0; k < card; k++) {
        wr16v(&cs[i].payload, v);
        v += 1 + rnd() % 8;
        if (v < 8) break;  // wrapped; card shrinks below — fix card
      }
      cs[i].card = cs[i].payload.size() / 2;
    } else if (t == 1) {  // bitmap
      cs[i].payload.resize(8192);
      int card = 0;
      for (int w = 0; w < 8192; w++) {
        uint8_t byte = (w % 3 == 0) ? (rnd() & 0xFF) : 0;
        cs[i].payload[w] = byte;
        card += __builtin_popcount(byte);
      }
      if (card == 0) {
        cs[i].payload[0] = 1;
        card = 1;
      }
      cs[i].card = card;
    } else {  // run: (start, length) pairs
      run_flags[i / 8] |= 1 << (i % 8);
      int rn = 1 + rnd() % 16;
      wr16v(&cs[i].payload, rn);
      uint32_t v = rnd() % 64;
      int card = 0;
      for (int r = 0; r < rn; r++) {
        uint32_t length = rnd() % 32;
        if (v + length > 0xFFFF) {
          v = 0;
          length = 1;
        }
        wr16v(&cs[i].payload, v);
        wr16v(&cs[i].payload, length);
        card += length + 1;
        v += length + 2 + rnd() % 16;
      }
      cs[i].card = card;
    }
  }
  std::vector<uint8_t> buf;
  bool have_offsets;
  if (with_runs) {
    wr32v(&buf, 12347u | ((n_cont - 1) << 16));
    buf.insert(buf.end(), run_flags.begin(), run_flags.end());
    have_offsets = n_cont >= 4;
  } else {
    wr32v(&buf, 12346u);
    wr32v(&buf, n_cont);
    have_offsets = true;
  }
  for (auto& c : cs) {
    wr16v(&buf, c.key);
    wr16v(&buf, c.card - 1);
  }
  size_t off_at = buf.size();
  if (have_offsets) buf.resize(buf.size() + 4 * n_cont);
  for (int i = 0; i < n_cont; i++) {
    if (have_offsets) {
      uint32_t o = buf.size();
      memcpy(&buf[off_at + 4 * i], &o, 4);
    }
    buf.insert(buf.end(), cs[i].payload.begin(), cs[i].payload.end());
  }
  return buf;
}

void mutate(std::vector<uint8_t>* buf) {
  if (buf->empty()) return;
  switch (rnd() % 5) {
    case 0: {  // flip random bytes
      int k = 1 + rnd() % 8;
      for (int i = 0; i < k; i++)
        (*buf)[rnd() % buf->size()] ^= 1 << (rnd() % 8);
      break;
    }
    case 1:  // truncate
      buf->resize(rnd() % buf->size());
      break;
    case 2: {  // splice random garbage
      size_t at = rnd() % buf->size();
      int k = 1 + rnd() % 16;
      for (int i = 0; i < k && at + i < buf->size(); i++)
        (*buf)[at + i] = rnd() & 0xFF;
      break;
    }
    case 3: {  // tweak a 16-bit length-ish field
      if (buf->size() >= 10) {
        size_t at = 4 + rnd() % (buf->size() - 6);
        uint16_t v = rnd() % 5 == 0 ? 0xFFFF : (rnd() & 0xFF);
        memcpy(&(*buf)[at], &v, 2);
      }
      break;
    }
    case 4:  // extend with garbage
      for (int i = 0; i < 32; i++) buf->push_back(rnd() & 0xFF);
      break;
  }
}

void one_case(const std::vector<uint8_t>& buf, bool valid) {
  int64_t n = roaring_decode_count(buf.data(), buf.size());
  if (n < 0) {
    if (valid) {
      fprintf(stderr, "decode_count rejected a VALID buffer\n");
      abort();
    }
    return;
  }
  if (n > (1 << 26)) return;  // absurd-but-bounded claim: skip alloc
  std::vector<uint64_t> out(n ? n : 1);
  int64_t got = roaring_decode(buf.data(), buf.size(), out.data(), n);
  if (got > n) {
    fprintf(stderr, "decode overran promised capacity: %lld > %lld\n",
            (long long)got, (long long)n);
    abort();
  }
  if (valid && got != n) {
    fprintf(stderr, "decode of a VALID buffer returned %lld, claimed %lld\n",
            (long long)got, (long long)n);
    abort();
  }
}

// Sanitizer exercise of the bulk-import scatters (ASan/UBSan build):
// random shapes through both entry points, including the staged
// write-combining partition and the inline-count paths.
void scatter_case() {
  int exp = 14 + rnd() % 3;                       // small shard widths
  int64_t wps = (1LL << exp) / 32;
  int64_t n_shards = 1 + rnd() % 40;
  int64_t n = 1 + rnd() % 300000;                 // crosses the 2^18 gate
  std::vector<uint64_t> cols(n);
  uint64_t span = (n_shards + 1) << exp;          // some out-of-range
  for (auto& c : cols) c = rnd() % span;
  std::vector<uint32_t> blocks(n_shards * wps, 0);
  std::vector<uint8_t> touched(n_shards, 0);
  std::vector<int64_t> counts(n_shards, 0);
  scatter_row_blocks(cols.data(), n, exp, blocks.data(), n_shards, wps,
                     touched.data(), counts.data());
  int depth = 1 + rnd() % 20;
  std::vector<int64_t> vals(n);
  for (auto& v : vals)
    v = (int64_t)(rnd() % (1ULL << depth)) - (1LL << (depth - 1));
  std::vector<uint32_t> bblocks(n_shards * (depth + 2) * wps, 0);
  std::fill(touched.begin(), touched.end(), 0);
  std::vector<int64_t> bcounts(n_shards * (depth + 2), 0);
  scatter_bsi_blocks(cols.data(), vals.data(), n, exp, depth,
                     bblocks.data(), n_shards, wps, touched.data(),
                     bcounts.data());
}

// Sanitizer exercise of the one-call stack build: random sources, some
// positions at or past the row width (ignored, never written), and now
// and then a destination row outside the matrix or a negative length,
// which must be refused before anything is written.
void rows_case() {
  int64_t n_words = 1 + rnd() % 512;
  int64_t n_rows = 1 + rnd() % 24;
  int64_t n = rnd() % 40;
  std::vector<std::vector<uint64_t>> rows(n);
  std::vector<uint64_t> src(n);
  std::vector<int64_t> len(n), dst(n);
  uint64_t span = static_cast<uint64_t>(n_words) * 32;
  for (int64_t k = 0; k < n; k++) {
    rows[k].resize(rnd() % 3000);
    for (auto& p : rows[k])
      p = (rnd() % 9 == 0) ? span + rnd() % (1ULL << (rnd() % 58))
                           : rnd() % span;
    src[k] = reinterpret_cast<uintptr_t>(rows[k].data());
    len[k] = static_cast<int64_t>(rows[k].size());
    dst[k] = rnd() % n_rows;
  }
  bool bad = n > 0 && rnd() % 4 == 0;
  if (bad) {
    int64_t k = rnd() % n;
    switch (rnd() % 3) {
      case 0: dst[k] = n_rows + rnd() % 5; break;
      case 1: dst[k] = -1 - static_cast<int64_t>(rnd() % 5); break;
      default: len[k] = -1 - static_cast<int64_t>(rnd() % 5);
    }
  }
  std::vector<uint32_t> mat(n_rows * n_words, 0);
  int rc = positions_to_rows(mat.data(), n_rows, n_words, src.data(),
                             len.data(), dst.data(), n);
  if (bad) {
    bool written = std::any_of(mat.begin(), mat.end(),
                               [](uint32_t w) { return w != 0; });
    if (rc == 0 || written) {
      fprintf(stderr, "positions_to_rows took a row outside the matrix\n");
      abort();
    }
    return;
  }
  std::vector<uint32_t> want(n_rows * n_words, 0);
  for (int64_t k = 0; k < n; k++)
    for (uint64_t p : rows[k])
      if (p < span) want[dst[k] * n_words + (p >> 5)] |= 1u << (p & 31);
  if (rc != 0 || mat != want) {
    fprintf(stderr, "positions_to_rows: wrong matrix\n");
    abort();
  }
}

}  // namespace

int main(int argc, char** argv) {
  long iters = argc > 1 ? atol(argv[1]) : 100000;
  if (argc > 2) rng_state ^= atol(argv[2]);
  for (long i = 0; i < iters; i++) {
    std::vector<uint8_t> buf = (rnd() & 1) ? seed_pilosa() : seed_official();
    bool valid = i % 3 == 0;  // 1/3 stay valid (decode must ACCEPT them)
    if (!valid) {
      int k = 1 + rnd() % 4;
      for (int m = 0; m < k; m++) mutate(&buf);
    }
    one_case(buf, valid);
    if (i % 2000 == 0) scatter_case();
    if (i % 50 == 0) rows_case();
  }
  printf("fuzz_roaring: %ld iterations clean\n", iters);
  return 0;
}

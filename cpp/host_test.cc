// Sanitizer harness for the C++ host runtime (SURVEY §5 race-detection
// axis: "TPU build: rely on C++ TSAN/ASAN in tests"). Exercises every
// extern-C entry point — hashing, partition permutation, slot-directory
// resolve (hit + miss + dedup paths), the pane slide, JSON-lines parsing
// incl. malformed input, and a multi-threaded framed-TCP data-plane
// roundtrip — under
// -fsanitize=address,undefined (make asan-test) and =thread
// (make tsan-test). Plain asserts; exit 0 = clean.
#include <cassert>
#include <cstdint>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <thread>
#include <vector>

extern "C" {
void ah_hash_u64(const uint64_t*, uint64_t*, int64_t);
void ah_hash_combine(uint64_t*, const uint64_t*, int64_t);
void ah_hash_f64(const double*, uint64_t*, int64_t);
int ah_partition(const uint64_t*, int64_t, int32_t, int64_t*, int64_t*);
int64_t ah_dir_max_bins();
int64_t ah_dir_resolve(const int64_t*, const void*, int32_t, int64_t,
                       const uint64_t*, const int64_t*, const int64_t*,
                       int64_t, int64_t, const int64_t*, const int64_t*,
                       void*, int32_t, int64_t, int64_t,
                       int64_t*, uint64_t*, int64_t*, int64_t*,
                       int64_t*, int64_t*, int64_t*);
int64_t ah_dir_claim(const uint64_t*, const int64_t*, const int64_t*, int64_t,
                     uint64_t*, int64_t*, int64_t*, int64_t, int64_t,
                     int64_t*, int64_t*, const int64_t*, int64_t,
                     int64_t*, void*, int32_t, const int64_t*, int64_t);
int64_t ah_pane_slide(const int64_t*, int64_t, int64_t,
                      const int64_t*, const int64_t* const*, int64_t,
                      const int64_t*, const int64_t* const*, int64_t,
                      int32_t, int32_t, int64_t*, int64_t);
int64_t ah_bin_combine(const int64_t*, int64_t, int64_t, int32_t, const int32_t*,
                       const int32_t*, const void* const*, int64_t*, int64_t);
int64_t ah_step_max_bins();
int64_t ah_step_make(int64_t, const int64_t*, const int64_t* const*,
                     const int64_t* const*, int32_t, const int32_t*, const int32_t*,
                     const void* const*, int64_t, int32_t, int32_t, int64_t,
                     int64_t, int64_t*, int32_t*, void* const*,
                     const int64_t*, const double*, int64_t*);
int64_t ah_parse_json_lines(const char*, int64_t, int32_t, const char*,
                            const int32_t*, int64_t, int64_t**, double**,
                            uint8_t**, int64_t**, char**, int64_t*);
void ah_free(void*);
int dp_listen(const char*, int);
int dp_bound_port(int);
int dp_accept(int);
int dp_connect(const char*, int, int, int);
int dp_send_frame(int, uint32_t, uint32_t, uint32_t, uint32_t, uint32_t,
                  const char*, uint32_t);
int dp_recv_header(int, uint32_t*);
int dp_recv_payload(int, char*, uint32_t);
void dp_close(int);
}

static void test_hashing() {
  const int64_t n = 1000;
  std::vector<uint64_t> in(n), a(n), b(n);
  for (int64_t i = 0; i < n; i++) in[i] = (uint64_t)(i * 37);
  ah_hash_u64(in.data(), a.data(), n);
  ah_hash_u64(in.data(), b.data(), n);
  for (int64_t i = 0; i < n; i++) assert(a[i] == b[i]);
  assert(a[0] != a[1]);
  ah_hash_combine(a.data(), b.data(), n);
  for (int64_t i = 0; i < n; i++) assert(a[i] != b[i]);
  std::vector<double> f(n);
  for (int64_t i = 0; i < n; i++) f[i] = i * 0.5 - 10.0;
  f[1] = -0.0;  // must hash like +0.0
  f[2] = 0.0;
  ah_hash_f64(f.data(), a.data(), n);
  assert(a[1] == a[2]);
}

static void test_partition() {
  const int64_t n = 4096;
  const int32_t nd = 8;
  std::vector<uint64_t> h(n);
  for (int64_t i = 0; i < n; i++) h[i] = (uint64_t)(i * 2654435761u);
  std::vector<int64_t> perm(n), offsets(nd + 1);
  assert(ah_partition(h.data(), n, nd, perm.data(), offsets.data()) == 0);
  assert(offsets[0] == 0 && offsets[nd] == n);
  std::vector<char> seen(n, 0);
  for (int64_t i = 0; i < n; i++) {
    assert(perm[i] >= 0 && perm[i] < n && !seen[perm[i]]);
    seen[perm[i]] = 1;
  }
  for (int32_t d = 0; d < nd; d++) assert(offsets[d] <= offsets[d + 1]);
}

// the slot a resolved row must hold: the one whose identity is the row's
static int32_t slot_of(const std::vector<int64_t>& keys,
                       const std::vector<int64_t>& bins, int64_t i,
                       const std::vector<int64_t>& slot_keys,
                       const std::vector<int64_t>& slot_bins) {
  for (size_t s = 0; s < slot_keys.size(); s++)
    if (slot_keys[s] == keys[i] && slot_bins[s] == bins[i]) return (int32_t)s;
  return -1;
}

static void test_dir_resolve() {
  const int64_t n = 512, hcap = 2048, nslots = 1024;
  std::vector<int64_t> keys(n), bins(n);
  std::vector<int32_t> bins32(n);
  for (int64_t i = 0; i < n; i++) { keys[i] = i % 100; bins32[i] = bins[i] = i % 3; }
  // empty directory: everything misses, deduped to distinct (key,bin)
  std::vector<uint64_t> hcode(hcap, 0);
  std::vector<int64_t> hbin(hcap, -1), hslot(hcap, -1);
  std::vector<int64_t> slot_keys(nslots, -1), slot_bins(nslots, -1);
  std::vector<int64_t> out_slots(n), miss_ord(n), miss_keys(n), miss_bins(n);
  std::vector<uint64_t> miss_codes(n);
  const int64_t max_bins = ah_dir_max_bins();
  std::vector<int64_t> bin_vals(max_bins), bin_counts(max_bins);
  int64_t nb = 0;
  auto resolve = [&](const void* b, int32_t narrow, int64_t boundary) {
    return ah_dir_resolve(keys.data(), b, narrow, n, hcode.data(), hbin.data(),
                          hslot.data(), hcap, boundary, slot_keys.data(),
                          slot_bins.data(), out_slots.data(), 0, n, 0, miss_ord.data(),
                          miss_codes.data(), miss_keys.data(), miss_bins.data(),
                          bin_vals.data(), bin_counts.data(), &nb);
  };
  int64_t m = resolve(bins.data(), 0, 0);
  assert(m == 300);  // 100 keys x 3 bins distinct misses
  for (int64_t i = 0; i < n; i++) assert(out_slots[i] < 0);
  for (int64_t i = 0; i < n; i++) assert(miss_ord[i] >= 0 && miss_ord[i] < m);
  // the misses' bins in the order met, a count each
  assert(nb == 3 && bin_vals[0] == 0 && bin_vals[1] == 1 && bin_vals[2] == 2);
  assert(bin_counts[0] == 100 && bin_counts[1] == 100 && bin_counts[2] == 100);

  // claim: bin 0 gets two ranges, bin 1 one that runs out after 60 slots,
  // bin 2 none: its groups and bin 1's last 40 stay at -1 and enter nothing
  const int64_t ranges[] = {0, 0, 64, 0, 128, 36, 1, 256, 60};
  std::vector<int64_t> miss_slots(m);
  auto claim = [&](const int64_t* rs, int64_t nr, int64_t boundary) {
    return ah_dir_claim(miss_codes.data(), miss_keys.data(), miss_bins.data(), m,
                        hcode.data(), hbin.data(), hslot.data(), hcap, boundary,
                        slot_keys.data(), slot_bins.data(),
                        rs, nr, miss_slots.data(), out_slots.data(), 0,
                        miss_ord.data(), n);
  };
  int64_t left = claim(ranges, 3, 0);
  int64_t placed = 0, unplaced_rows = 0;
  for (int64_t j = 0; j < m; j++) {
    const int64_t s = miss_slots[j];
    if (s < 0) { assert(miss_bins[j] != 0); continue; }
    placed++;
    assert(slot_keys[s] == miss_keys[j] && slot_bins[s] == miss_bins[j]);
    if (miss_bins[j] == 0) assert((s >= 0 && s < 64) || (s >= 128 && s < 164));
    else assert(miss_bins[j] == 1 && s >= 256 && s < 316);
  }
  assert(placed == 160);
  for (int64_t i = 0; i < n; i++) {
    if (out_slots[i] < 0) { unplaced_rows++; continue; }
    assert(slot_keys[out_slots[i]] == keys[i] && slot_bins[out_slots[i]] == bins[i]);
  }
  assert(left == unplaced_rows && left > 0);
  int64_t entries = 0;
  for (int64_t h = 0; h < hcap; h++) entries += hslot[h] >= 0;
  assert(entries == 160);

  // resolved again (bins as int32 this time): the placed groups hit their
  // slots, the rest miss again, bins 1 and 2 alone
  std::vector<int64_t> first(out_slots);
  m = resolve(bins32.data(), 1, 0);
  assert(m == 140 && nb == 2 && bin_counts[0] + bin_counts[1] == 140);
  for (int64_t i = 0; i < n; i++) assert(out_slots[i] == first[i]);
  const int64_t rest[] = {1, 512, 100, 2, 640, 100};
  miss_slots.resize(m);
  assert(claim(rest, 2, 0) == 0);
  for (int64_t i = 0; i < n; i++)
    assert(slot_keys[out_slots[i]] == keys[i] && slot_bins[out_slots[i]] == bins[i]);
  assert(resolve(bins.data(), 0, 0) == 0 && nb == 0);

  // a close raised the boundary: bins 0 and 1 are dead and their positions
  // claimable; their groups miss, and a group of bin 2 hits its slot unless
  // a dead entry now ends its probe path before it (it then takes a second)
  m = resolve(bins.data(), 0, 2);
  assert(m >= 200 && nb >= 2);
  for (int64_t i = 0; i < n; i++)
    assert(out_slots[i] < 0 ? true : (bins[i] == 2 && slot_keys[out_slots[i]] == keys[i]));
  for (int64_t i = 0; i < n; i++) assert(bins[i] == 2 || out_slots[i] < 0);
  // more distinct bins than a claim takes: the count says so
  std::vector<int64_t> many(n);
  for (int64_t i = 0; i < n; i++) many[i] = 10 + i % (max_bins + 1);
  assert(resolve(many.data(), 0, 2) > 0 && nb == -1);

  // the slots in the step's own index type, padded to its width: int32, the
  // entries past the rows at the capacity the scatter drops
  const int64_t room = n + 9;
  std::vector<int32_t> narrow(room, 7);
  m = ah_dir_resolve(keys.data(), bins32.data(), 1, n, hcode.data(), hbin.data(),
                     hslot.data(), hcap, 0, slot_keys.data(), slot_bins.data(),
                     narrow.data(), 1, room, nslots, miss_ord.data(), miss_codes.data(),
                     miss_keys.data(), miss_bins.data(), bin_vals.data(),
                     bin_counts.data(), &nb);
  assert(m == 0);
  for (int64_t i = 0; i < n; i++) assert(narrow[i] == slot_of(keys, bins, i, slot_keys, slot_bins));
  for (int64_t i = n; i < room; i++) assert(narrow[i] == nslots);
}

// A window of NB bins slid over a stream of bins against the same window
// combined anew from its bins: lanes sum (may pass 0 and below), count, and
// a carried key column; keys leave and come back, bins go missing.
static void test_pane_slide() {
  const int NB = 4, BINS = 40, L = 3, ADDED = 2;
  struct Bin { std::vector<int64_t> keys, lane[L]; };
  std::vector<Bin> bins(BINS);
  uint64_t rnd = 12345;
  auto next = [&]() { rnd = rnd * 6364136223846793005ull + 1442695040888963407ull; return rnd >> 33; };
  for (int b = 0; b < BINS; b++) {
    if (b % 7 == 3 || b == 20 || b == 23) continue;  // bins that held no row
    for (int64_t key = -6; key < 6; key++) {
      if (next() % 3 == 0) continue;
      bins[b].keys.push_back(key);
      bins[b].lane[0].push_back((int64_t)(next() % 9) - 4);  // a sum, signed
      bins[b].lane[1].push_back(1 + (int64_t)(next() % 3));  // a count
      bins[b].lane[2].push_back(key * 10);                   // the key's column
    }
  }
  auto ptrs = [](const Bin& bin, const int64_t** p) {
    for (int l = 0; l < L; l++) p[l] = bin.lane[l].data();
  };
  auto full = [&](int w, std::vector<int64_t>& blk, int64_t stride) {
    int64_t m = 0;
    for (int64_t key = -6; key < 6; key++) {
      int64_t pres = 0, sum = 0, cnt = 0;
      for (int b = w; b < w + NB && b < BINS; b++)
        for (size_t x = 0; x < bins[b].keys.size(); x++)
          if (bins[b].keys[x] == key) {
            pres++;
            sum += bins[b].lane[0][x];
            cnt += bins[b].lane[1][x];
          }
      if (!pres) continue;
      blk[m] = key; blk[stride + m] = pres; blk[2 * stride + m] = sum;
      blk[3 * stride + m] = cnt; blk[4 * stride + m] = key * 10;
      m++;
    }
    return m;
  };
  const int64_t stride = 16;
  std::vector<int64_t> state((2 + L) * stride), want((2 + L) * stride);
  int64_t n = full(0, state, stride);
  for (int w = 1; w + NB <= BINS; w++) {
    const Bin& out_bin = bins[w - 1];
    const Bin& in_bin = bins[w + NB - 1];
    const int64_t *ap[L], *rp[L];
    ptrs(in_bin, ap);
    ptrs(out_bin, rp);
    const int64_t o_stride = n + (int64_t)in_bin.keys.size();
    std::vector<int64_t> out((2 + L) * (o_stride ? o_stride : 1));
    int64_t m = ah_pane_slide(state.data(), stride, n,
                              in_bin.keys.data(), ap, (int64_t)in_bin.keys.size(),
                              out_bin.keys.data(), rp, (int64_t)out_bin.keys.size(),
                              L, ADDED, out.data(), o_stride);
    int64_t wm = full(w, want, stride);
    assert(m == wm);
    for (int row = 0; row < 2 + L; row++)
      for (int64_t x = 0; x < m; x++)
        assert(out[row * o_stride + x] == want[row * stride + x]);
    n = m;
    for (int row = 0; row < 2 + L; row++)
      for (int64_t x = 0; x < m; x++) state[row * stride + x] = out[row * o_stride + x];
  }
  // what the pass refuses: -1, and the caller combines anew
  const int64_t st[] = {1, 5, 9, 0,  2, 1, 1, 0,  7, 3, 4, 0,  10, 50, 90, 0};
  int64_t out[4 * 8];
  const int64_t two[] = {5, 5}, back[] = {9, 5}, vals[] = {3, 4}, col[] = {50, 50};
  const int64_t col9[] = {90, 50}, bad_col[] = {51};
  const int64_t *l_two[] = {vals, col}, *l_back[] = {vals, col9}, *l_bad[] = {vals, bad_col};
  auto slide = [&](const int64_t* ak, const int64_t* const* al, int64_t a,
                   const int64_t* rk, const int64_t* const* rl, int64_t r, int64_t room) {
    return ah_pane_slide(st, 4, 3, ak, al, a, rk, rl, r, 2, 1, out, room);
  };
  assert(slide(two, l_two, 2, nullptr, nullptr, 0, 8) == -1);    // a key twice
  assert(slide(back, l_back, 2, nullptr, nullptr, 0, 8) == -1);  // out of order
  assert(slide(nullptr, nullptr, 0, two, l_two, 2, 8) == -1);    // retiring twice
  assert(slide(nullptr, nullptr, 0, back, l_back, 2, 8) == -1);
  const int64_t absent[] = {4}, *l_abs[] = {vals, col};
  assert(slide(nullptr, nullptr, 0, absent, l_abs, 1, 8) == -1);  // the state lacks it
  assert(slide(absent, l_abs, 1, absent, l_abs, 1, 8) == -1);     // though it comes in
  const int64_t last[] = {12}, *l_last[] = {vals, col};
  assert(slide(nullptr, nullptr, 0, last, l_last, 1, 8) == -1);   // past the state's end
  const int64_t five[] = {5};
  assert(slide(five, l_bad, 1, nullptr, nullptr, 0, 8) == -1);    // its column differs
  assert(slide(five, l_two, 1, nullptr, nullptr, 0, 3) == -1);    // no room for n + a
  // key 5 leaves with its last bin, but that bin gave it 4 of its 3
  const int64_t four[] = {4}, *l_four[] = {four, col};
  assert(slide(nullptr, nullptr, 0, five, l_four, 1, 8) == -1);
  // and what it takes: key 5 in again (presence 2), key 1's one of two bins out
  const int64_t one[] = {1}, sev[] = {2}, c1[] = {10}, *l_one[] = {sev, c1};
  assert(slide(five, l_two, 1, one, l_one, 1, 8) == 3);
  assert(out[0] == 1 && out[1] == 5 && out[2] == 9);
  assert(out[8] == 1 && out[9] == 2 && out[10] == 1);
  assert(out[16] == 5 && out[17] == 6 && out[18] == 4);
  assert(out[24] == 10 && out[25] == 50 && out[26] == 90);
}

// ah_bin_combine against a plain loop: one partial a bin for each kind, over
// 4- and 8-byte lanes
static void test_bin_combine() {
  const int64_t CAP = 8, BIN = 1000;
  const int32_t kinds[] = {0, 1, 2, 3, 0, 3};      // sum count min max | sum32 max32
  const int32_t widths[] = {8, 0, 8, 8, 4, 4};
  const int32_t L = 6;
  std::vector<int64_t> out((2 + L) * CAP);
  auto run = [&](const std::vector<int64_t>& ts, const std::vector<int64_t>& v,
                 const std::vector<int32_t>& w, int64_t cap) {
    const void* lanes[] = {v.data(), nullptr, v.data(), v.data(), w.data(), w.data()};
    return ah_bin_combine(ts.data(), (int64_t)ts.size(), BIN, L, kinds, widths, lanes,
                          out.data(), cap);
  };
  // an empty batch: no bin
  assert(run({}, {}, {}, CAP) == 0);
  // one bin, and event times below zero: a bin is the floor of the division
  {
    std::vector<int64_t> ts = {-1, -1000, -500}, v = {7, -9, 4};
    std::vector<int32_t> w = {7, -9, 4};
    assert(run(ts, v, w, CAP) == 1);
    assert(out[0] == -1 && out[CAP] == 3);
    assert(out[2 * CAP] == 2 && out[3 * CAP] == 3 && out[4 * CAP] == -9 && out[5 * CAP] == 7);
    assert(out[6 * CAP] == 2 && out[7 * CAP] == 7);
  }
  // several bins, out of order and in runs of one: against the plain loop
  {
    const int64_t n = 5000;
    std::vector<int64_t> ts(n), v(n);
    std::vector<int32_t> w(n);
    uint64_t x = 42;
    for (int64_t i = 0; i < n; i++) {
      x = x * 6364136223846793005ull + 1442695040888963407ull;
      const int64_t bin = 10 + (i / 900) + ((x >> 60) == 0 ? -2 : 0);  // some rows two bins behind
      ts[i] = bin * BIN + (int64_t)((x >> 20) % BIN);
      v[i] = (int64_t)(x >> 1) - (int64_t)(1ull << 62);
      w[i] = (int32_t)(x >> 33) - (1 << 30);
    }
    const int64_t m = run(ts, v, w, CAP);
    assert(m > 2 && m <= CAP);
    int64_t rows = 0;
    for (int64_t b = 0; b < m; b++) {
      uint64_t sum = 0, sum32 = 0;
      int64_t cnt = 0, mn = INT64_MAX, mx = INT64_MIN, mx32 = INT64_MIN;
      for (int64_t i = 0; i < n; i++) {
        if (ts[i] / BIN != out[b]) continue;  // no event time below zero here
        sum += (uint64_t)v[i];
        sum32 += (uint64_t)(int64_t)w[i];
        cnt++;
        mn = v[i] < mn ? v[i] : mn;
        mx = v[i] > mx ? v[i] : mx;
        mx32 = w[i] > mx32 ? w[i] : mx32;
      }
      assert(cnt > 0 && out[CAP + b] == cnt && out[3 * CAP + b] == cnt);
      assert(out[2 * CAP + b] == (int64_t)sum && out[4 * CAP + b] == mn);
      assert(out[5 * CAP + b] == mx && out[6 * CAP + b] == (int64_t)sum32);
      assert(out[7 * CAP + b] == mx32);
      for (int64_t c = 0; c < b; c++) assert(out[c] != out[b]);  // a bin once
      rows += cnt;
    }
    assert(rows == n);
    // more distinct bins than the caller has room for: refused, numpy's then
    assert(run(ts, v, w, m - 1) == -1);
  }
  // what it does not take: a kind or a width it does not know, no bin width
  {
    const int64_t ts[] = {5}, v[] = {1};
    const void* lanes[] = {v};
    int64_t o[3 * 2];
    const int32_t sum = 0, avg = 4, eight = 8, two = 2;
    assert(ah_bin_combine(ts, 1, BIN, 1, &sum, &eight, lanes, o, 2) == 1 && o[4] == 1);
    assert(ah_bin_combine(ts, 1, BIN, 1, &avg, &eight, lanes, o, 2) == -1);
    assert(ah_bin_combine(ts, 1, BIN, 1, &sum, &two, lanes, o, 2) == -1);
    assert(ah_bin_combine(ts, 1, 0, 1, &sum, &eight, lanes, o, 2) == -1);
  }
}

// ah_step_make against a plain loop: two pieces, late rows in the middle of
// one, three bins, a count lane that ships nothing, casts, the padded tails
static void test_step_make() {
  const int64_t BIN = 1000, ROOM = 16;
  const int64_t ts0[] = {5100, 5200, 2100, 6100, 5300};   // 2100 is late
  const int64_t ts1[] = {7100, 7200, 6900};
  const int64_t k0[] = {1, 2, 3, 4, 5}, k1[] = {6, 7, 8};
  const int64_t v0[] = {10, -20, 30, 40, 50}, v1[] = {60, 70, -80};
  const float f0[] = {1.5f, 2.5f, 3.5f, 4.5f, 5.5f}, f1[] = {6.5f, 7.5f, 8.5f};
  const int64_t rows[] = {5, 3};
  const int64_t* ts[] = {ts0, ts1};
  const int64_t* keys[] = {k0, k1};
  // lanes: a count (nothing), int64 -> int32 max, float -> double min
  const int32_t src[] = {1, 1, 2}, dst[] = {1, 0, 3};
  const void* cols[] = {nullptr, nullptr, v0, v1, f0, f1};
  int64_t out_keys[ROOM];
  int32_t out_rel[ROOM], lane1[ROOM];
  double lane2[ROOM];
  void* outs[] = {nullptr, lane1, lane2};
  const int64_t ident_i[] = {0, INT32_MIN, 0};
  const double ident_f[] = {0, 0, 1e300};
  std::vector<int64_t> info(3 + ah_step_max_bins());
  info[2] = 3;  // the base: bin 3
  int64_t m = ah_step_make(2, rows, ts, keys, 3, src, dst, cols, BIN, 1, 1, 2, ROOM,
                           out_keys, out_rel, outs, ident_i, ident_f, info.data());
  assert(m == 7 && info[0] == 1 && info[1] == 3 && info[2] == 3);
  assert(info[3] == 2 && info[4] == 3 && info[5] == 4);
  const int64_t want_k[] = {1, 2, 4, 5, 6, 7, 8}, want_v[] = {10, -20, 40, 50, 60, 70, -80};
  const int32_t want_r[] = {2, 2, 3, 2, 4, 4, 3};
  const double want_f[] = {1.5, 2.5, 4.5, 5.5, 6.5, 7.5, 8.5};
  for (int i = 0; i < 7; i++)
    assert(out_keys[i] == want_k[i] && out_rel[i] == want_r[i] && lane1[i] == want_v[i]
           && lane2[i] == want_f[i]);
  for (int i = 7; i < ROOM; i++) assert(lane1[i] == INT32_MIN && lane2[i] == 1e300);
  // not anchored: the least bin of these rows is the base, nothing is late
  m = ah_step_make(2, rows, ts, nullptr, 3, src, dst, cols, BIN, 0, 0, 0, ROOM,
                   out_keys, out_rel, outs, ident_i, ident_f, info.data());
  assert(m == 8 && info[0] == 0 && info[2] == 2 && info[1] == 4 && info[3] == 3 && info[4] == 0);
  assert(out_keys[2] == 0 && out_rel[2] == 0 && lane1[2] == 30);
  // every row late; more rows than the step has room for; a float into an integer lane
  m = ah_step_make(2, rows, ts, keys, 3, src, dst, cols, BIN, 1, 1, 100, ROOM,
                   out_keys, out_rel, outs, ident_i, ident_f, info.data());
  assert(m == 0 && info[0] == 8 && info[1] == 0 && lane1[0] == INT32_MIN);
  assert(ah_step_make(2, rows, ts, keys, 3, src, dst, cols, BIN, 1, 0, 0, 7,
                      out_keys, out_rel, outs, ident_i, ident_f, info.data()) == -1);
  const int32_t bad_dst[] = {1, 0, 1};
  assert(ah_step_make(2, rows, ts, keys, 3, src, bad_dst, cols, BIN, 1, 0, 0, ROOM,
                      out_keys, out_rel, outs, ident_i, ident_f, info.data()) == -1);
}

static void test_json() {
  const char* data =
      "{\"a\": 1, \"b\": 2.5, \"c\": true, \"d\": \"x\"}\n"
      "{\"a\": -7, \"b\": 0.25, \"c\": false, \"d\": \"hello world\"}\n";
  const char names[] = "a\0b\0c\0d\0";
  int32_t kinds[4] = {0, 1, 2, 3};
  std::vector<int64_t> ca(16), offs(17);
  std::vector<double> cb(16);
  std::vector<uint8_t> cc(16);
  int64_t* iptrs[4] = {ca.data(), nullptr, nullptr, nullptr};
  double* fptrs[4] = {nullptr, cb.data(), nullptr, nullptr};
  uint8_t* bptrs[4] = {nullptr, nullptr, cc.data(), nullptr};
  int64_t* optrs[4] = {nullptr, nullptr, nullptr, offs.data()};
  char* arena = nullptr;
  int64_t arena_len = 0;
  int64_t rows = ah_parse_json_lines(data, (int64_t)strlen(data), 4,
                                     names, kinds, 16, iptrs, fptrs, bptrs,
                                     optrs, &arena, &arena_len);
  assert(rows == 2);
  assert(ca[0] == 1 && ca[1] == -7);
  assert(cb[0] == 2.5 && cb[1] == 0.25);
  assert(cc[0] == 1 && cc[1] == 0);
  assert(arena_len > 0);
  assert(strncmp(arena + offs[0], "x", 1) == 0);
  ah_free(arena);
  // malformed input: error, no leak, no crash
  const char* bad = "{\"a\": }\n";
  arena = nullptr;
  int64_t r2 = ah_parse_json_lines(bad, (int64_t)strlen(bad), 4, names,
                                   kinds, 16, iptrs, fptrs, bptrs, optrs,
                                   &arena, &arena_len);
  assert(r2 < 0);
  if (arena) ah_free(arena);
}

static void test_data_plane() {
  int lfd = dp_listen("127.0.0.1", 0);
  assert(lfd >= 0);
  int port = dp_bound_port(lfd);
  assert(port > 0);
  const int kFrames = 200;
  std::thread server([&] {
    int c = dp_accept(lfd);
    assert(c >= 0);
    uint32_t hdr[6];
    for (int i = 0; i < kFrames; i++) {
      assert(dp_recv_header(c, hdr) == 0);
      assert((int)hdr[0] == i && hdr[4] == 0u);
      std::vector<char> payload(hdr[5]);
      if (hdr[5]) assert(dp_recv_payload(c, payload.data(), hdr[5]) == 0);
      if (hdr[5]) assert(payload[0] == (char)('a' + i % 26));
    }
    assert(dp_recv_header(c, hdr) == -1);  // clean close
    dp_close(c);
  });
  int fd = dp_connect("127.0.0.1", port, 10, 20);
  assert(fd >= 0);
  for (int i = 0; i < kFrames; i++) {
    std::vector<char> payload(1 + i % 512, (char)('a' + i % 26));
    assert(dp_send_frame(fd, (uint32_t)i, 1, 2, 3, 0, payload.data(),
                         (uint32_t)payload.size()) == 0);
  }
  dp_close(fd);
  server.join();
  dp_close(lfd);
}

int main() {
  test_hashing();
  test_partition();
  test_dir_resolve();
  test_pane_slide();
  test_bin_combine();
  test_step_make();
  test_json();
  test_data_plane();
  printf("host_test OK\n");
  return 0;
}

// arroyo-tpu C++ host runtime.
//
// Native equivalents of the reference engine's hot host-side paths, which in
// the reference are Rust inside arroyo-worker/arroyo-operator:
//   - 64-bit key hashing            (context.rs:512 create_hashes analog;
//                                    splitmix64 mix, matching hashing.py)
//   - keyed repartition permutation (context.rs:502-556 repartition)
//   - JSON-lines columnar parsing   (arroyo-formats de.rs hot loop)
//   - framed TCP data plane         (worker/src/network_manager.rs: 24-byte
//                                    header + payload per frame)
//
// Exposed as a plain C ABI consumed via ctypes (arroyo_tpu/native). The
// compute path stays JAX/XLA; this library owns the byte-shoveling
// around it.

#include <cerrno>
#include <cstdint>
#include <cstring>
#include <cstdio>
#include <cstdlib>

#include <arpa/inet.h>
#include <netdb.h>
#include <netinet/in.h>
#include <netinet/tcp.h>
#include <sys/socket.h>
#include <sys/types.h>
#include <unistd.h>

extern "C" {

// ---------------------------------------------------------------- hashing

static const uint64_t C1 = 0x9E3779B97F4A7C15ull;
static const uint64_t C2 = 0xBF58476D1CE4E5B9ull;
static const uint64_t C3 = 0x94D049BB133111EBull;

static inline uint64_t splitmix64(uint64_t x) {
  uint64_t z = x + C1;
  z = (z ^ (z >> 30)) * C2;
  z = (z ^ (z >> 27)) * C3;
  return z ^ (z >> 31);
}

// out[i] = splitmix64(in[i])
void ah_hash_u64(const uint64_t* in, uint64_t* out, int64_t n) {
  for (int64_t i = 0; i < n; i++) out[i] = splitmix64(in[i]);
}

// h[i] = splitmix64(h[i] ^ (h2[i] + C1)) — column combine (hashing.py:74)
void ah_hash_combine(uint64_t* h, const uint64_t* h2, int64_t n) {
  for (int64_t i = 0; i < n; i++) h[i] = splitmix64(h[i] ^ (h2[i] + C1));
}

// float canonicalization: -0.0 -> 0.0, then bitcast (hashing.py:60-62)
void ah_hash_f64(const double* in, uint64_t* out, int64_t n) {
  for (int64_t i = 0; i < n; i++) {
    double v = in[i] == 0.0 ? 0.0 : in[i];
    uint64_t bits;
    memcpy(&bits, &v, 8);
    out[i] = splitmix64(bits);
  }
}

// ------------------------------------------------------------ repartition

// Counting-sort permutation of rows by destination subtask.
// dests_out[i] = min(hash[i] / size, n_dest-1); perm is a stable ordering of
// row indices grouped by destination; offsets[d]..offsets[d+1] delimit
// destination d's rows in perm. Returns 0 on success.
int ah_partition(const uint64_t* hashes, int64_t n_rows, int32_t n_dest,
                 int64_t* perm, int64_t* offsets /* n_dest+1 */) {
  if (n_dest <= 0) return -1;
  if (n_dest == 1) {
    // size would be 2^64 (wraps to 0): everything goes to destination 0
    for (int64_t i = 0; i < n_rows; i++) perm[i] = i;
    offsets[0] = 0;
    offsets[1] = n_rows;
    return 0;
  }
  const uint64_t size = 0xFFFFFFFFFFFFFFFFull / (uint64_t)n_dest + 1;
  // counts
  for (int32_t d = 0; d <= n_dest; d++) offsets[d] = 0;
  // reuse perm as scratch for per-row destination to avoid a second pass
  for (int64_t i = 0; i < n_rows; i++) {
    uint64_t d = hashes[i] / size;
    if (d >= (uint64_t)n_dest) d = n_dest - 1;
    perm[i] = (int64_t)d;
    offsets[d + 1]++;
  }
  for (int32_t d = 0; d < n_dest; d++) offsets[d + 1] += offsets[d];
  // stable scatter
  int64_t* cursor = (int64_t*)malloc(sizeof(int64_t) * n_dest);
  if (!cursor) return -2;
  for (int32_t d = 0; d < n_dest; d++) cursor[d] = offsets[d];
  // second buffer for output permutation
  int64_t* out = (int64_t*)malloc(sizeof(int64_t) * (n_rows ? n_rows : 1));
  if (!out) { free(cursor); return -2; }
  for (int64_t i = 0; i < n_rows; i++) {
    int64_t d = perm[i];
    out[cursor[d]++] = i;
  }
  memcpy(perm, out, sizeof(int64_t) * n_rows);
  free(out);
  free(cursor);
  return 0;
}

// -------------------------------------------------------- slot directory

// One-pass resolve over the BinSlotDirectory's open-addressing arrays
// (arroyo_tpu/ops/slot_agg.py BinSlotDirectory: hcode/hbin/hslot parallel
// arrays). Probe semantics mirror the numpy fallback lookup_or_assign:
// code = splitmix64(key ^ bin*C1); a live entry (hslot >= 0 && hbin >=
// boundary) with matching code resolves (identity-checked against
// slot_keys/slot_bins; mismatch = 64-bit collision -> -2); the first
// non-live probe position means the group has no slot yet -> MISS.
//
// ``bins`` is int64, or int32 where bins_narrow is set (the window
// operators' relative bins: no widened copy of a step's rows on the host).
// ``out_slots`` is int64, or int32 where slots_narrow is set, and ``room``
// >= n entries long: the entries past the rows are set to ``pad`` (a step
// made to the device's shapes: the table's capacity, which the scatter
// drops), so the array is the step's index input as it stands.
//
// Misses are deduplicated by code in stream order: out_slots[i] = -1 and
// miss_ord[i] = index into miss_codes/miss_keys/miss_bins (length = return
// value). Their distinct bins, in the order met, go to miss_bin_vals with a
// count each in miss_bin_counts (AH_DIR_MAX_BINS entries each) and their
// number to *n_miss_bins: Python takes each bin's slots from its allocator
// as ranges and ah_dir_claim places the misses. More distinct bins than
// that sets *n_miss_bins = -1, and the caller allocates each first-seen
// group through BinSlotDirectory.lookup_or_assign and scatters the new
// slots back through miss_ord. Returns the miss count, -2 on identity
// collision, -3 when a probe wraps the full table (caller falls back to
// numpy).
enum { AH_DIR_MAX_BINS = 64 };

int64_t ah_dir_max_bins() { return AH_DIR_MAX_BINS; }

static inline int64_t bin_at(const void* bins, int32_t narrow, int64_t i) {
  return narrow ? (int64_t)((const int32_t*)bins)[i] : ((const int64_t*)bins)[i];
}

static inline void slot_put(void* slots, int32_t narrow, int64_t i, int64_t v) {
  if (narrow) ((int32_t*)slots)[i] = (int32_t)v; else ((int64_t*)slots)[i] = v;
}

int64_t ah_dir_resolve(
    const int64_t* keys, const void* bins, int32_t bins_narrow, int64_t n,
    const uint64_t* hcode, const int64_t* hbin, const int64_t* hslot,
    int64_t hcap, int64_t boundary,
    const int64_t* slot_keys, const int64_t* slot_bins,
    void* out_slots, int32_t slots_narrow, int64_t room, int64_t pad,
    int64_t* miss_ord,
    uint64_t* miss_codes, int64_t* miss_keys, int64_t* miss_bins,
    int64_t* miss_bin_vals, int64_t* miss_bin_counts, int64_t* n_miss_bins) {
  const uint64_t hmask = (uint64_t)hcap - 1;
  // local dedup table for missed codes (ord = -1 marks empty)
  int64_t dcap = 64;
  while (dcap < 2 * n) dcap <<= 1;
  const uint64_t dmask = (uint64_t)dcap - 1;
  uint64_t* dcode = (uint64_t*)malloc(sizeof(uint64_t) * dcap);
  int64_t* dord = (int64_t*)malloc(sizeof(int64_t) * dcap);
  if (!dcode || !dord) { free(dcode); free(dord); return -4; }
  for (int64_t j = 0; j < dcap; j++) dord[j] = -1;
  int64_t m = 0;
  int64_t rc = 0;
  int64_t nb = 0;    // distinct bins among the misses; -1 = too many
  int64_t last = 0;  // where the last miss's bin stands: a stream runs in bins
  for (int64_t i = 0; i < n; i++) {
    const int64_t key = keys[i];
    const int64_t bin = bin_at(bins, bins_narrow, i);
    const uint64_t code = splitmix64((uint64_t)key ^ ((uint64_t)bin * C1));
    uint64_t h = code & hmask;
    int64_t slot = -1;
    bool miss = false;
    int64_t step = 0;
    for (; step < hcap; step++) {
      if (hslot[h] < 0 || hbin[h] < boundary) { miss = true; break; }
      if (hcode[h] == code) {
        const int64_t s = hslot[h];
        if (slot_keys[s] != key || slot_bins[s] != bin) { rc = -2; goto done; }
        slot = s;
        break;
      }
      h = (h + 1) & hmask;
    }
    if (slot < 0 && !miss) { rc = -3; goto done; }  // table wrapped
    if (miss) {
      uint64_t dh = code & dmask;
      while (dord[dh] >= 0 && dcode[dh] != code) dh = (dh + 1) & dmask;
      if (dord[dh] < 0) {
        dcode[dh] = code;
        dord[dh] = m;
        miss_codes[m] = code;
        miss_keys[m] = key;
        miss_bins[m] = bin;
        m++;
        if (nb >= 0) {
          if (last >= nb || miss_bin_vals[last] != bin) {
            for (last = 0; last < nb && miss_bin_vals[last] != bin; last++) {}
            if (last == nb) {
              if (nb == AH_DIR_MAX_BINS) {
                nb = -1;
              } else {
                miss_bin_vals[nb] = bin;
                miss_bin_counts[nb] = 0;
                nb++;
              }
            }
          }
          if (nb >= 0) miss_bin_counts[last]++;
        }
      }
      miss_ord[i] = dord[dh];
    }
    slot_put(out_slots, slots_narrow, i, slot);
  }
  for (int64_t i = n; i < room; i++) slot_put(out_slots, slots_narrow, i, pad);
  *n_miss_bins = nb;
  rc = m;
done:
  free(dcode);
  free(dord);
  return rc;
}

// Place the misses of one ah_dir_resolve: what lookup_or_assign does in
// rounds of numpy, in stream order. ``ranges`` holds n_ranges triples (bin,
// first slot, count): the slots Python's allocator
// (BinSlotDirectory._alloc_ranges, the one place that knows the region
// policy) set aside for each of the misses' bins, a bin's ranges together
// and in the order they are to be used. Each miss claims the first position
// on its probe path that is not live (hslot < 0 || hbin < boundary: what
// ah_dir_resolve calls a miss), takes the next slot of its bin's ranges,
// writes hcode/hbin/hslot and the slot's identity; a miss whose bin has no
// range left stays at -1 and enters nothing (the caller grows the table and
// resolves its rows again, or spills them). Then every row that
// ah_dir_resolve left at -1 gets its group's slot through miss_ord.
// miss_slots: scratch of m entries; out_slots as ah_dir_resolve wrote it
// (int32 where slots_narrow is set). Returns the rows still at -1, -3 when a
// probe wraps the full table (it cannot: the table has four positions a
// slot), -5 on ranges of more than AH_DIR_MAX_BINS bins.
int64_t ah_dir_claim(
    const uint64_t* miss_codes, const int64_t* miss_keys,
    const int64_t* miss_bins, int64_t m,
    uint64_t* hcode, int64_t* hbin, int64_t* hslot,
    int64_t hcap, int64_t boundary,
    int64_t* slot_keys, int64_t* slot_bins,
    const int64_t* ranges, int64_t n_ranges,
    int64_t* miss_slots, void* out_slots, int32_t slots_narrow,
    const int64_t* miss_ord, int64_t n) {
  const uint64_t hmask = (uint64_t)hcap - 1;
  // per bin: the range in use, the slots taken of it, one past its last
  int64_t bin_vals[AH_DIR_MAX_BINS], cur[AH_DIR_MAX_BINS], end[AH_DIR_MAX_BINS];
  int64_t used[AH_DIR_MAX_BINS];
  int64_t n_bins = 0;
  for (int64_t r = 0; r < n_ranges; r++) {
    if (n_bins == 0 || bin_vals[n_bins - 1] != ranges[3 * r]) {
      if (n_bins == AH_DIR_MAX_BINS) return -5;
      bin_vals[n_bins] = ranges[3 * r];
      cur[n_bins] = r;
      used[n_bins] = 0;
      n_bins++;
    }
    end[n_bins - 1] = r + 1;
  }
  int64_t b = 0;  // where the last miss's bin stands: a stream runs in bins
  for (int64_t j = 0; j < m; j++) {
    const int64_t bin = miss_bins[j];
    if (b >= n_bins || bin_vals[b] != bin)
      for (b = 0; b < n_bins && bin_vals[b] != bin; b++) {}
    miss_slots[j] = -1;
    if (b == n_bins) continue;  // no region was left for this bin at all
    while (cur[b] < end[b] && used[b] >= ranges[3 * cur[b] + 2]) {
      cur[b]++;
      used[b] = 0;
    }
    if (cur[b] >= end[b]) continue;  // its ranges ran out
    const uint64_t code = miss_codes[j];
    uint64_t h = code & hmask;
    int64_t step = 0;
    for (; step < hcap; step++) {
      if (hslot[h] < 0 || hbin[h] < boundary) break;
      h = (h + 1) & hmask;
    }
    if (step == hcap) return -3;
    const int64_t slot = ranges[3 * cur[b] + 1] + used[b]++;
    hcode[h] = code;
    hbin[h] = bin;
    hslot[h] = slot;
    slot_keys[slot] = miss_keys[j];
    slot_bins[slot] = bin;
    miss_slots[j] = slot;
  }
  int64_t unplaced = 0;
  for (int64_t i = 0; i < n; i++) {
    if (bin_at(out_slots, slots_narrow, i) < 0) {
      const int64_t s = miss_slots[miss_ord[i]];
      slot_put(out_slots, slots_narrow, i, s);
      if (s < 0) unplaced++;
    }
  }
  return unplaced;
}

// ---------------------------------------------------- a window that slides
//
// A sliding aggregate's close (arroyo_tpu/windows/sliding.py _slide): the
// combined rows of window w - 1, less the bin that window started with, plus
// the bin window w ends with, in one merge of three runs sorted by signed
// key with one row a key. ``state`` is the rows of window w - 1 as a block of
// 2 + n_lanes rows of s_stride int64 each, n of them filled: the keys, each
// key's presence (in how many of the window's bins it holds a row: a sum may
// be 0 while its key is still in the window), then one row a lane. The first
// n_added lanes add and subtract (8-byte integers; wrap-around as numpy's),
// the rest are a key's own columns, the same in every bin, and are carried.
// ``a_*`` is the bin coming in and ``r_*`` the one retiring (keys, and one
// pointer a lane; a or r is 0 for a bin that held no row). A key whose
// presence reaches 0 leaves, one the state lacks enters in order. Writes
// ``out``, a block like ``state`` of o_stride >= n + a, and returns the rows
// written. The state is read only: the window it stood for left in a batch
// that still points into it.
//
// The order relied on is checked on the way: a bin out of order or with a
// key twice, a retiring row whose key the state lacks or whose presence is
// used up, a key that leaves with more than its last bin gave it, a carried
// lane that differs from the state's: -1, and the caller combines the
// window's bins anew.
int64_t ah_pane_slide(
    const int64_t* state, int64_t s_stride, int64_t n,
    const int64_t* a_keys, const int64_t* const* a_lanes, int64_t a,
    const int64_t* r_keys, const int64_t* const* r_lanes, int64_t r,
    int32_t n_lanes, int32_t n_added,
    int64_t* out, int64_t o_stride) {
  if (o_stride < n + a) return -1;
  const int64_t* s_keys = state;
  const int64_t* s_pres = state + s_stride;
  int64_t i = 0, j = 0, k = 0, m = 0;
  while (i < n || j < a) {
    const bool in_s = i < n && (j >= a || s_keys[i] <= a_keys[j]);
    const bool in_a = j < a && (i >= n || a_keys[j] <= s_keys[i]);
    const int64_t key = in_s ? s_keys[i] : a_keys[j];
    if (in_a && j + 1 < a && a_keys[j + 1] <= key) return -1;
    // a retiring key below this one is in neither run: the state lacks it
    if (k < r && r_keys[k] < key) return -1;
    const bool in_r = k < r && r_keys[k] == key;
    if (in_r && (!in_s || (k + 1 < r && r_keys[k + 1] <= key))) return -1;
    const int64_t pres = (in_s ? s_pres[i] : 0) + in_a - in_r;
    if (pres < 0) return -1;
    if (pres == 0) {
      // its last bin retires: what that bin added is all it held
      for (int32_t l = 0; l < n_added; l++)
        if (state[(2 + l) * s_stride + i] != r_lanes[l][k]) return -1;
    } else {
      out[m] = key;
      out[o_stride + m] = pres;
      for (int32_t l = 0; l < n_lanes; l++) {
        const int64_t* s_lane = state + (2 + l) * s_stride;
        int64_t* o_lane = out + (2 + l) * o_stride;
        if (l < n_added) {
          uint64_t v = in_s ? (uint64_t)s_lane[i] : 0;
          if (in_a) v += (uint64_t)a_lanes[l][j];
          if (in_r) v -= (uint64_t)r_lanes[l][k];
          o_lane[m] = (int64_t)v;
        } else {
          if (in_s && in_a && s_lane[i] != a_lanes[l][j]) return -1;
          o_lane[m] = in_s ? s_lane[i] : a_lanes[l][j];
        }
      }
      m++;
    }
    i += in_s;
    j += in_a;
    k += in_r;
  }
  return k < r ? -1 : m;
}

// ------------------------------------------- a keyless aggregate's stage
//
// An aggregate the plan gives no key holds one slot a bin, so what a batch
// of its rows adds to the table is one partial a bin: ah_bin_combine makes
// them in one pass, the bin division of the timestamps and the reduce of
// every lane (windows/tumbling.py StagedAggregate._stage_partials). ``ts``
// are the rows' event times, a bin is floor(ts / bin_micros). Lane l is
// ``lanes[l]``, signed integers ``widths[l]`` bytes wide (4 or 8), reduced
// as ``kinds[l]`` says: 0 a sum (wrap-around as numpy's), 1 a count (the
// rows; its pointer is not read), 2 a min, 3 a max. ``out`` is a block of
// 2 + n_lanes rows of max_bins int64 each: the distinct bins in the order
// they were met, the rows of each, then lane l's partial of each (a sum of a
// 4-byte lane wraps as the lane would once it is cast back). Returns the
// bins written, or -1 for a batch of more than ``max_bins`` distinct bins, a
// lane width or a kind it does not know: the caller reduces with numpy.
//
// Rows of one bin come in runs (event time is near enough in order), so a
// row is compared with the last bin's bounds and divided only where it
// leaves them, and each lane is reduced a run at a time.

extern "C++" {
template <typename T>
static inline void lane_reduce(int32_t kind, const T* v, int64_t i, int64_t j,
                               int64_t* acc) {
  if (kind == 0) {
    uint64_t s = (uint64_t)*acc;
    for (int64_t k = i; k < j; k++) s += (uint64_t)(int64_t)v[k];
    *acc = (int64_t)s;
  } else if (kind == 2) {
    int64_t m = *acc;
    for (int64_t k = i; k < j; k++) m = v[k] < m ? (int64_t)v[k] : m;
    *acc = m;
  } else {
    int64_t m = *acc;
    for (int64_t k = i; k < j; k++) m = v[k] > m ? (int64_t)v[k] : m;
    *acc = m;
  }
}
}  // extern "C++"

static inline int64_t floor_div(int64_t a, int64_t b) {
  int64_t q = a / b;
  return (a % b != 0 && (a < 0) != (b < 0)) ? q - 1 : q;
}

int64_t ah_bin_combine(
    const int64_t* ts, int64_t n, int64_t bin_micros,
    int32_t n_lanes, const int32_t* kinds, const int32_t* widths,
    const void* const* lanes, int64_t* out, int64_t max_bins) {
  if (bin_micros <= 0) return -1;
  int64_t* out_bins = out;
  int64_t* out_rows = out + max_bins;
  int64_t* out_vals = out + 2 * max_bins;
  for (int32_t l = 0; l < n_lanes; l++) {
    if (kinds[l] < 0 || kinds[l] > 3) return -1;
    if (kinds[l] != 1 && widths[l] != 4 && widths[l] != 8) return -1;
  }
  int64_t m = 0;
  int64_t i = 0;
  while (i < n) {
    const int64_t bin = floor_div(ts[i], bin_micros);
    // the bin's event times are [lo, hi]
    const int64_t lo = bin * bin_micros, hi = lo + (bin_micros - 1);
    int64_t j = i + 1;
    while (j < n && ts[j] >= lo && ts[j] <= hi) j++;
    int64_t b = m - 1;  // the last bin met is the likeliest
    while (b >= 0 && out_bins[b] != bin) b--;
    if (b < 0) {
      if (m == max_bins) return -1;
      b = m++;
      out_bins[b] = bin;
      out_rows[b] = 0;
      for (int32_t l = 0; l < n_lanes; l++)
        out_vals[l * max_bins + b] =
            kinds[l] == 2 ? INT64_MAX : kinds[l] == 3 ? INT64_MIN : 0;
    }
    out_rows[b] += j - i;
    for (int32_t l = 0; l < n_lanes; l++) {
      int64_t* acc = out_vals + l * max_bins + b;
      if (kinds[l] == 1)
        *acc += j - i;
      else if (widths[l] == 8)
        lane_reduce(kinds[l], (const int64_t*)lanes[l], i, j, acc);
      else
        lane_reduce(kinds[l], (const int32_t*)lanes[l], i, j, acc);
    }
    i = j;
  }
  return m;
}

// ------------------------------------------------ a keyed aggregate's step
//
// The hook of a window aggregate over one staged step of rows
// (arroyo_tpu/windows/tumbling.py StagedAggregate._make_step), in one pass
// over the staged batches where they lie: the bin of every row, the late
// boundary, the keys, and each shipped accumulator lane cast to the lane's
// type and padded to the step's width with the lane's identity, which is what
// the device step takes (ops/slot_agg.py). Piece p (a staged batch, or the
// part of one that fits the step) has ``rows[p]`` rows, event times
// ``ts[p]`` and keys ``keys[p]`` (8 bytes each; ``keys`` null: the rows carry
// none and every key is 0). Lane l reads ``cols[l * n_pieces + p]``, a column
// of type ``src[l]`` (0 int32, 1 int64, 2 float32, 3 float64), and writes
// ``out_lanes[l]`` of type ``dst[l]``, ``room`` entries long: the kept rows
// in arrival order, then ``ident_i[l]`` (an integer lane) or ``ident_f[l]`` (a
// float one) to the end. A lane whose ``out_lanes[l]`` is null ships nothing
// (a count: the device adds one a row). A float column is never cast to an
// integer lane (-1): numpy's answer out of range is the platform's.
//
// A row's bin is floor(ts / bin_micros) less ``info[2]``, the bin space's
// base; where ``anchored`` is 0 the base is first set to the least bin of
// these rows (the stream's first rows anchor the bin space alone). A row
// whose relative bin is below ``late_before`` (compared in int64; ``has_late``
// 0: no boundary yet) is late: counted and left out. Writes the kept rows'
// keys and relative bins (int32) to ``out_keys`` / ``out_rel`` and to ``info``
// [0] the late rows, [1] the distinct bins among the kept, [2] the base,
// [3..] the distinct bins in the order met. Returns the rows kept; -1 for more rows than ``room``, more than
// AH_STEP_MAX_BINS distinct bins, or a type it does not take: nothing is
// then to be read from the outputs and the caller runs its numpy hook.
//
// Rows of one bin come in runs, so a row is compared with the last bin's
// bounds and divided only where it leaves them.
enum { AH_STEP_MAX_BINS = 64 };

int64_t ah_step_max_bins() { return AH_STEP_MAX_BINS; }

extern "C++" {
template <typename S, typename D>
static inline void lane_take(const void* col, int64_t r, const int64_t* kept,
                             int64_t k, void* out, int64_t at) {
  const S* v = (const S*)col;
  D* o = (D*)out + at;
  if (kept == nullptr) {
    for (int64_t i = 0; i < r; i++) o[i] = (D)v[i];
  } else {
    for (int64_t j = 0; j < k; j++) o[j] = (D)v[kept[j]];
  }
}

template <typename D>
static inline void lane_fill(void* out, int64_t from, int64_t to, D v) {
  D* o = (D*)out;
  for (int64_t i = from; i < to; i++) o[i] = v;
}

struct RowScratch {  // freed wherever the pass returns
  int64_t* rows = nullptr;
  ~RowScratch() { free(rows); }
};
}  // extern "C++"

int64_t ah_step_make(
    int64_t n_pieces, const int64_t* rows,
    const int64_t* const* ts, const int64_t* const* keys,
    int32_t n_lanes, const int32_t* src, const int32_t* dst,
    const void* const* cols,
    int64_t bin_micros, int32_t anchored, int32_t has_late, int64_t late_before,
    int64_t room, int64_t* out_keys, int32_t* out_rel, void* const* out_lanes,
    const int64_t* ident_i, const double* ident_f, int64_t* info) {
  if (bin_micros <= 0) return -1;
  int64_t total = 0;
  for (int64_t p = 0; p < n_pieces; p++) total += rows[p];
  if (total > room) return -1;
  for (int32_t l = 0; l < n_lanes; l++) {
    if (out_lanes[l] == nullptr) continue;
    if (src[l] < 0 || src[l] > 3 || dst[l] < 0 || dst[l] > 3) return -1;
    if (src[l] >= 2 && dst[l] < 2) return -1;
  }
  int64_t base = info[2];
  if (!anchored) {
    bool any = false;
    int64_t least = 0;
    for (int64_t p = 0; p < n_pieces; p++)
      for (int64_t i = 0; i < rows[p]; i++)
        if (!any || ts[p][i] < least) { least = ts[p][i]; any = true; }
    if (!any) return -1;
    base = floor_div(least, bin_micros);
  }
  int64_t* bins = info + 3;
  int64_t nb = 0, cur = -1, late = 0, m = 0;
  int64_t lo = 1, hi = 0;  // the event times of the bin the last row was in: none yet
  int64_t rel = 0;
  bool is_late = false;
  RowScratch kept;  // a piece's kept rows, once one of its rows was late
  for (int64_t p = 0; p < n_pieces; p++) {
    const int64_t r = rows[p], m0 = m;
    const int64_t* t = ts[p];
    const int64_t* key = keys ? keys[p] : nullptr;
    bool cut = false;
    int64_t k = 0;  // rows of this piece in ``kept``
    for (int64_t i = 0; i < r; i++) {
      if (t[i] < lo || t[i] > hi) {
        const int64_t bin = floor_div(t[i], bin_micros);
        lo = bin * bin_micros;
        hi = lo + (bin_micros - 1);
        rel = bin - base;
        is_late = has_late && rel < late_before;
        if (!is_late && (cur < 0 || bins[cur] != rel)) {
          for (cur = 0; cur < nb && bins[cur] != rel; cur++) {}
          if (cur == nb) {
            if (nb == AH_STEP_MAX_BINS) return -1;
            bins[nb++] = rel;
          }
        }
      }
      if (is_late) {
        late++;
        if (!cut) {
          cut = true;
          if (kept.rows == nullptr) {
            kept.rows = (int64_t*)malloc(sizeof(int64_t) * room);
            if (kept.rows == nullptr) return -1;
          }
          for (k = 0; k < i; k++) kept.rows[k] = k;
        }
        continue;
      }
      if (cut) kept.rows[k++] = i;
      out_keys[m] = key ? key[i] : 0;
      out_rel[m] = (int32_t)rel;
      m++;
    }
    const int64_t* pick = cut ? kept.rows : nullptr;
    if (m == m0) continue;
    for (int32_t l = 0; l < n_lanes; l++) {
      void* o = out_lanes[l];
      if (o == nullptr) continue;
      const void* c = cols[l * n_pieces + p];
#define AH_TAKE(S, D) lane_take<S, D>(c, r, pick, k, o, m0); break
      switch (src[l] * 4 + dst[l]) {
        case 0: AH_TAKE(int32_t, int32_t);
        case 1: AH_TAKE(int32_t, int64_t);
        case 2: AH_TAKE(int32_t, float);
        case 3: AH_TAKE(int32_t, double);
        case 4: AH_TAKE(int64_t, int32_t);
        case 5: AH_TAKE(int64_t, int64_t);
        case 6: AH_TAKE(int64_t, float);
        case 7: AH_TAKE(int64_t, double);
        case 10: AH_TAKE(float, float);
        case 11: AH_TAKE(float, double);
        case 14: AH_TAKE(double, float);
        case 15: AH_TAKE(double, double);
        default: return -1;
      }
#undef AH_TAKE
    }
  }
  for (int32_t l = 0; l < n_lanes; l++) {
    void* o = out_lanes[l];
    if (o == nullptr) continue;
    switch (dst[l]) {
      case 0: lane_fill<int32_t>(o, m, room, (int32_t)ident_i[l]); break;
      case 1: lane_fill<int64_t>(o, m, room, ident_i[l]); break;
      case 2: lane_fill<float>(o, m, room, (float)ident_f[l]); break;
      default: lane_fill<double>(o, m, room, ident_f[l]); break;
    }
  }
  info[0] = late;
  info[1] = nb;
  info[2] = base;
  return m;
}

// ------------------------------------------------------------- JSON lines
//
// Flat-object parser for a fixed schema. Column kinds:
//   0 = int64, 1 = float64, 2 = bool, 3 = string, 4 = skip/ignore
// For string columns the caller gets (offsets into a shared byte arena).
// Missing keys yield 0 / NaN / false / empty. Returns rows parsed, or
// -(line_index+1) on malformed input.

struct StrArena {
  char* data;
  int64_t len;
  int64_t cap;
};

static int arena_push(StrArena* a, const char* s, int64_t n) {
  if (a->len + n > a->cap) {
    int64_t ncap = a->cap * 2;
    if (ncap < a->len + n) ncap = a->len + n + 4096;
    char* nd = (char*)realloc(a->data, ncap);
    if (!nd) return -1;
    a->data = nd;
    a->cap = ncap;
  }
  memcpy(a->data + a->len, s, n);
  a->len += n;
  return 0;
}

static const char* skip_ws(const char* p, const char* end) {
  while (p < end && (*p == ' ' || *p == '\t' || *p == '\r')) p++;
  return p;
}

// parse a JSON string starting at the opening quote; unescapes into buf
// (caller-sized >= input length). Returns pointer past closing quote, or
// nullptr on error; *out_len = unescaped length.
static const char* parse_string(const char* p, const char* end, char* buf,
                                int64_t* out_len) {
  if (p >= end || *p != '"') return nullptr;
  p++;
  int64_t n = 0;
  while (p < end && *p != '"') {
    if (*p == '\\' && p + 1 < end) {
      p++;
      char c = *p++;
      switch (c) {
        case 'n': buf[n++] = '\n'; break;
        case 't': buf[n++] = '\t'; break;
        case 'r': buf[n++] = '\r'; break;
        case 'b': buf[n++] = '\b'; break;
        case 'f': buf[n++] = '\f'; break;
        case '"': buf[n++] = '"'; break;
        case '\\': buf[n++] = '\\'; break;
        case '/': buf[n++] = '/'; break;
        case 'u': {
          if (p + 4 > end) return nullptr;
          unsigned cp = 0;
          for (int k = 0; k < 4; k++) {
            char h = p[k];
            cp <<= 4;
            if (h >= '0' && h <= '9') cp |= h - '0';
            else if (h >= 'a' && h <= 'f') cp |= h - 'a' + 10;
            else if (h >= 'A' && h <= 'F') cp |= h - 'A' + 10;
            else return nullptr;
          }
          p += 4;
          // utf-8 encode (BMP only; surrogate pairs pass through as-is)
          if (cp < 0x80) buf[n++] = (char)cp;
          else if (cp < 0x800) {
            buf[n++] = (char)(0xC0 | (cp >> 6));
            buf[n++] = (char)(0x80 | (cp & 0x3F));
          } else {
            buf[n++] = (char)(0xE0 | (cp >> 12));
            buf[n++] = (char)(0x80 | ((cp >> 6) & 0x3F));
            buf[n++] = (char)(0x80 | (cp & 0x3F));
          }
          break;
        }
        default: return nullptr;
      }
    } else {
      buf[n++] = *p++;
    }
  }
  if (p >= end) return nullptr;
  *out_len = n;
  return p + 1;  // past closing quote
}

// skip any JSON value (for unknown keys / nested objects)
static const char* skip_value(const char* p, const char* end) {
  p = skip_ws(p, end);
  if (p >= end) return nullptr;
  if (*p == '"') {
    p++;
    while (p < end && *p != '"') {
      if (*p == '\\') p++;
      p++;
    }
    return p < end ? p + 1 : nullptr;
  }
  if (*p == '{' || *p == '[') {
    char open = *p, close = (*p == '{') ? '}' : ']';
    int depth = 0;
    bool in_str = false;
    while (p < end) {
      if (in_str) {
        if (*p == '\\') p++;
        else if (*p == '"') in_str = false;
      } else if (*p == '"') in_str = true;
      else if (*p == open) depth++;
      else if (*p == close) {
        depth--;
        if (depth == 0) return p + 1;
      }
      p++;
    }
    return nullptr;
  }
  while (p < end && *p != ',' && *p != '}' && *p != ']' && *p != ' ' &&
         *p != '\n' && *p != '\t' && *p != '\r')
    p++;
  return p;
}

// data: newline-separated JSON objects. Schema: n_cols columns with names
// (concatenated, NUL-separated) and kinds. Outputs: per-column arrays sized
// max_rows; string columns write (str_offsets[col][row+1] ends) into one
// shared arena returned via *arena_out/*arena_len (caller frees with
// ah_free). Bool columns are uint8. null -> 0/NaN/false/empty.
int64_t ah_parse_json_lines(const char* data, int64_t data_len,
                            int32_t n_cols, const char* names_blob,
                            const int32_t* kinds, int64_t max_rows,
                            int64_t** int_cols, double** f64_cols,
                            uint8_t** bool_cols, int64_t** str_offsets,
                            char** arena_out, int64_t* arena_len) {
  // resolve column names
  const char* names[64];
  int64_t name_lens[64];
  if (n_cols > 64) return -1000000;
  {
    const char* p = names_blob;
    for (int32_t c = 0; c < n_cols; c++) {
      names[c] = p;
      name_lens[c] = strlen(p);
      p += name_lens[c] + 1;
    }
  }
  StrArena arena = {(char*)malloc(4096), 0, 4096};
  if (!arena.data) return -1000001;
  char* strbuf = (char*)malloc(data_len + 8);
  if (!strbuf) { free(arena.data); return -1000001; }

  // initialize string offsets row 0
  for (int32_t c = 0; c < n_cols; c++)
    if (kinds[c] == 3) str_offsets[c][0] = 0;

  const char* p = data;
  const char* end = data + data_len;
  int64_t row = 0;
  int64_t line_no = 0;
  while (p < end && row < max_rows) {
    const char* line_end = (const char*)memchr(p, '\n', end - p);
    if (!line_end) line_end = end;
    const char* q = skip_ws(p, line_end);
    if (q == line_end) { p = line_end + 1; line_no++; continue; }
    if (*q != '{') goto fail;
    q++;
    // defaults for this row
    for (int32_t c = 0; c < n_cols; c++) {
      switch (kinds[c]) {
        case 0: int_cols[c][row] = 0; break;
        case 1: f64_cols[c][row] = __builtin_nan(""); break;
        case 2: bool_cols[c][row] = 0; break;
        case 3: str_offsets[c][row + 1] = arena.len; break;
        default: break;
      }
    }
    while (true) {
      q = skip_ws(q, line_end);
      if (q < line_end && *q == '}') { q++; break; }
      int64_t klen;
      q = parse_string(q, line_end, strbuf, &klen);
      if (!q) goto fail;
      q = skip_ws(q, line_end);
      if (q >= line_end || *q != ':') goto fail;
      q++;
      q = skip_ws(q, line_end);
      // find the column
      int32_t col = -1;
      for (int32_t c = 0; c < n_cols; c++) {
        if (name_lens[c] == klen && memcmp(names[c], strbuf, klen) == 0) {
          col = c;
          break;
        }
      }
      if (col < 0 || kinds[col] == 4) {
        q = skip_value(q, line_end);
        if (!q) goto fail;
      } else if (kinds[col] == 3) {
        if (q < line_end && *q == '"') {
          int64_t slen;
          q = parse_string(q, line_end, strbuf, &slen);
          if (!q) goto fail;
          if (arena_push(&arena, strbuf, slen) != 0) goto fail;
        } else {
          // null / non-string: empty string
          q = skip_value(q, line_end);
          if (!q) goto fail;
        }
        str_offsets[col][row + 1] = arena.len;
      } else if (q < line_end && (*q == 'n')) {  // null
        q = skip_value(q, line_end);
        if (!q) goto fail;
      } else if (kinds[col] == 2) {
        if (q + 4 <= line_end && memcmp(q, "true", 4) == 0) {
          bool_cols[col][row] = 1;
          q += 4;
        } else if (q + 5 <= line_end && memcmp(q, "false", 5) == 0) {
          bool_cols[col][row] = 0;
          q += 5;
        } else goto fail;
      } else {
        char* numend;
        if (kinds[col] == 0) {
          long long v = strtoll(q, &numend, 10);
          if (numend == q) goto fail;
          // float-typed input into int column: fall back to strtod
          if (numend < line_end && (*numend == '.' || *numend == 'e' || *numend == 'E')) {
            double dv = strtod(q, &numend);
            v = (long long)dv;
          }
          int_cols[col][row] = v;
        } else {
          double v = strtod(q, &numend);
          if (numend == q) goto fail;
          f64_cols[col][row] = v;
        }
        q = numend;
      }
      q = skip_ws(q, line_end);
      if (q < line_end && *q == ',') q++;
    }
    row++;
    line_no++;
    p = line_end + 1;
  }
  *arena_out = arena.data;
  *arena_len = arena.len;
  free(strbuf);
  return row;

fail:
  free(arena.data);
  free(strbuf);
  return -(line_no + 1);
}

void ah_free(void* p) { free(p); }

// -------------------------------------------------------------- data plane
//
// Frame layout (reference network_manager.rs:102-162 — 24-byte LE header):
//   u32 src_op | u32 src_subtask | u32 dst_op | u32 dst_subtask |
//   u32 msg_type | u32 len        then `len` payload bytes.

struct FrameHeader {
  uint32_t src_op, src_subtask, dst_op, dst_subtask, msg_type, len;
};

static int read_full(int fd, void* buf, size_t n) {
  char* p = (char*)buf;
  size_t got = 0;
  while (got < n) {
    ssize_t r = recv(fd, p + got, n - got, 0);
    if (r == 0) return -1;  // peer closed
    if (r < 0) {
      if (errno == EINTR) continue;
      return -2;
    }
    got += (size_t)r;
  }
  return 0;
}

static int write_full(int fd, const void* buf, size_t n) {
  const char* p = (const char*)buf;
  size_t sent = 0;
  while (sent < n) {
    ssize_t r = send(fd, p + sent, n - sent, MSG_NOSIGNAL);
    if (r < 0) {
      if (errno == EINTR) continue;
      return -2;
    }
    sent += (size_t)r;
  }
  return 0;
}

int dp_listen(const char* host, int port) {
  int fd = socket(AF_INET, SOCK_STREAM, 0);
  if (fd < 0) return -1;
  int one = 1;
  setsockopt(fd, SOL_SOCKET, SO_REUSEADDR, &one, sizeof(one));
  sockaddr_in addr{};
  addr.sin_family = AF_INET;
  addr.sin_port = htons((uint16_t)port);
  if (inet_pton(AF_INET, host, &addr.sin_addr) != 1) { close(fd); return -3; }
  if (bind(fd, (sockaddr*)&addr, sizeof(addr)) != 0) { close(fd); return -4; }
  if (listen(fd, 128) != 0) { close(fd); return -5; }
  return fd;
}

int dp_bound_port(int fd) {
  sockaddr_in addr{};
  socklen_t len = sizeof(addr);
  if (getsockname(fd, (sockaddr*)&addr, &len) != 0) return -1;
  return ntohs(addr.sin_port);
}

int dp_accept(int listen_fd) {
  int fd = accept(listen_fd, nullptr, nullptr);
  if (fd < 0) return -1;
  int one = 1;
  setsockopt(fd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof(one));
  return fd;
}

int dp_connect(const char* host, int port, int retries, int backoff_ms) {
  for (int attempt = 0; attempt <= retries; attempt++) {
    int fd = socket(AF_INET, SOCK_STREAM, 0);
    if (fd < 0) return -1;
    sockaddr_in addr{};
    addr.sin_family = AF_INET;
    addr.sin_port = htons((uint16_t)port);
    if (inet_pton(AF_INET, host, &addr.sin_addr) != 1) { close(fd); return -3; }
    if (connect(fd, (sockaddr*)&addr, sizeof(addr)) == 0) {
      int one = 1;
      setsockopt(fd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof(one));
      return fd;
    }
    close(fd);
    usleep((useconds_t)backoff_ms * 1000 * (attempt + 1));
  }
  return -2;
}

int dp_send_frame(int fd, uint32_t src_op, uint32_t src_sub, uint32_t dst_op,
                  uint32_t dst_sub, uint32_t msg_type, const char* payload,
                  uint32_t len) {
  FrameHeader h{src_op, src_sub, dst_op, dst_sub, msg_type, len};
  if (write_full(fd, &h, sizeof(h)) != 0) return -1;
  if (len && write_full(fd, payload, len) != 0) return -1;
  return 0;
}

// Two-phase receive so the caller can size the payload buffer exactly:
// dp_recv_header fills out_header[6] (src_op, src_sub, dst_op, dst_sub,
// msg_type, len); returns 0, -1 on clean close, -2 on error. Then
// dp_recv_payload reads exactly `len` bytes.
int dp_recv_header(int fd, uint32_t* out_header) {
  FrameHeader h;
  int r = read_full(fd, &h, sizeof(h));
  if (r != 0) return r == -1 ? -1 : -2;
  out_header[0] = h.src_op;
  out_header[1] = h.src_subtask;
  out_header[2] = h.dst_op;
  out_header[3] = h.dst_subtask;
  out_header[4] = h.msg_type;
  out_header[5] = h.len;
  return 0;
}

int dp_recv_payload(int fd, char* payload, uint32_t len) {
  if (len == 0) return 0;
  return read_full(fd, payload, len);
}

void dp_close(int fd) { close(fd); }

}  // extern "C"

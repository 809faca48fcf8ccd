/* launch_operands — a full-postings launch's fused operand in one call.
 *
 * es_pruned_operands writes the float32[S, B, 3*T + 3*P + 1] array that
 * distributed.pack_pruned_operands builds from prepare_query_batch and
 * prepare_term_ranges for a full-path launch (no prefix cap, no
 * compressed streams), byte for byte:
 *
 *   [0, T)          slot starts (int32 bits)
 *   [T, 2T)         slot lengths (int32 bits)
 *   [2T, 3T)        slot weights
 *   [3T, 3T+P)      the first P terms' postings starts (int32 bits)
 *   [3T+P, 3T+2P)   their lengths (int32 bits)
 *   [3T+2P, 3T+3P)  their weights
 *   [3T+3P]         the tail bound, 0 (nothing is truncated)
 *
 * The slot plan is sparse.plan_slots': L_c is the lane-based power of two
 * over the longest extent, capped at the largest such bucket within the
 * chunk cap; an extent longer than L_c is split into chunks of L_c, an
 * empty one keeps one zero-length slot; T is the next power of two over
 * the most slots a (shard row, query) needs, and at least the caller's.
 *
 * A term is a row of the pack's term table (distributed.TermTable): per
 * shard row its postings start and length, its weight at boost 1, whether
 * the row's vocabulary holds it, and its group idf (NaN where the group's
 * df is 0). Under a boost other than 1 a weight is boost * idf * (k1 + 1),
 * multiplied in that order in double precision as Python does.
 *
 * It touches no Python object, and the caller binds it holding the
 * interpreter lock (native.bind(..., hold_gil=True)): a launch's operand
 * takes it well under a millisecond, where a call that let go of the lock
 * would wait milliseconds to take it back from the request threads.
 */

#include <stdint.h>
#include <string.h>

static int64_t len_bucket(int64_t n, int64_t lane)
{
    int64_t b = lane;
    while (b < n)
        b *= 2;
    return b;
}

static int64_t cap_bucket(int64_t cap, int64_t lane)
{
    int64_t b = lane;
    while (b * 2 <= cap)
        b *= 2;
    return b;
}

static void put_i32(float *dst, int32_t v)
{
    memcpy(dst, &v, sizeof v);
}

static float weight_of(double boost, double w1, double idf, double k1p)
{
    if (boost == 1.0)
        return (float)w1;
    if (idf != idf) /* the group's df is 0 */
        return 0.0f;
    return (float)(boost * idf * k1p);
}

/* ids[offsets[q] .. offsets[q+1]) are query q's term rows, q < n_queries
 * ≤ rows; the table's columns are [n_columns, shards] row-major. Writes
 * out[shards, rows, 3*slots + 3*pad_terms + 1] and info = {T, L_c,
 * window, sum of the slots' lengths}.
 *
 * Returns 0; -T when the plan needs T > slots slots (nothing written);
 * -1 on an input out of range. */
int64_t es_pruned_operands(const int32_t *ids, const int32_t *offsets,
                           const double *boosts, int32_t n_queries,
                           const int32_t *col_start,
                           const int32_t *col_length,
                           const double *col_weight,
                           const uint8_t *col_held, const double *col_idf,
                           int32_t n_columns, int32_t shards, int32_t rows,
                           int32_t slots, int32_t pad_terms,
                           int64_t chunk_cap, int64_t lane, double k1p,
                           float *out, int64_t *info)
{
    if (n_queries < 0 || n_queries > rows || shards <= 0 || slots <= 0
            || pad_terms < 0 || lane <= 0 || offsets[0] != 0)
        return -1;
    int64_t window = 1, longest = 1;
    for (int32_t q = 0; q < n_queries; q++) {
        int64_t n = (int64_t)offsets[q + 1] - offsets[q];
        if (n < 0)
            return -1;
        if (n > window)
            window = n;
    }
    int64_t n_ids = offsets[n_queries];
    for (int64_t i = 0; i < n_ids; i++) {
        if (ids[i] < 0 || ids[i] >= n_columns)
            return -1;
        const int32_t *len = col_length + (int64_t)ids[i] * shards;
        for (int32_t si = 0; si < shards; si++)
            if (len[si] > longest)
                longest = len[si];
    }
    int64_t max_len = len_bucket(longest, lane);
    int64_t cap = cap_bucket(chunk_cap, lane);
    if (cap < max_len)
        max_len = cap;

    int64_t t_needed = 1;
    for (int32_t si = 0; si < shards; si++) {
        for (int32_t q = 0; q < n_queries; q++) {
            int64_t n = 0;
            for (int32_t i = offsets[q]; i < offsets[q + 1]; i++) {
                int64_t ln = col_length[(int64_t)ids[i] * shards + si];
                n += ln > max_len ? (ln + max_len - 1) / max_len : 1;
            }
            if (n > t_needed)
                t_needed = n;
        }
    }
    int64_t t_slots = 1;
    while (t_slots < t_needed)
        t_slots *= 2;
    if (t_slots < slots)
        t_slots = slots;
    if (t_slots > slots)
        return -t_slots;

    int64_t width = 3 * (int64_t)slots + 3 * (int64_t)pad_terms + 1;
    memset(out, 0, sizeof(float) * (size_t)shards * rows * width);
    float *const t_starts = out + 3 * (int64_t)slots;
    float *const t_lengths = t_starts + pad_terms;
    float *const t_weights = t_lengths + pad_terms;
    int64_t real = 0;
    for (int32_t si = 0; si < shards; si++) {
        for (int32_t q = 0; q < n_queries; q++) {
            int64_t base = ((int64_t)si * rows + q) * width;
            float *row = out + base;
            int64_t at = 0;
            for (int32_t i = offsets[q]; i < offsets[q + 1]; i++) {
                int64_t c = (int64_t)ids[i] * shards + si;
                int32_t st = col_start[c], ln = col_length[c];
                float w = weight_of(boosts[q], col_weight[c], col_idf[c], k1p);
                real += ln;
                int32_t off = 0;
                do {
                    int32_t take = ln - off < max_len ? ln - off
                                                      : (int32_t)max_len;
                    put_i32(row + at, st + off);
                    put_i32(row + slots + at, take);
                    row[2 * (int64_t)slots + at] = w;
                    at++;
                    off += take;
                } while (off < ln);
                int32_t term = i - offsets[q];
                if (term < pad_terms && col_held[c]) {
                    put_i32(t_starts + base + term, st);
                    put_i32(t_lengths + base + term, ln);
                    t_weights[base + term] = w;
                }
            }
        }
    }
    info[0] = t_slots;
    info[1] = max_len;
    info[2] = window;
    info[3] = real;
    return 0;
}

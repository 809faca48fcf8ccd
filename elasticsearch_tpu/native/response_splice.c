/* response_splice — assemble the hits-array JSON bytes from pre-encoded
 * columns without re-entering Python per hit.
 *
 * The serializer pre-encodes each column with ONE C-level json.dumps call
 * (ids as a string array, scores as a number array, index names as a
 * string array, per-hit residual fields as an object array).  This
 * splicer splits each encoded array into its top-level elements and
 * concatenates per-hit objects
 *
 *   {"_index":<name>,"_id":<id>,"_score":<score>[,<extras inner>]}
 *
 * byte-for-byte identical to json.dumps(hit_dict, separators=(",",":"))
 * of the materialized form, because every byte comes from a json.dumps
 * of the same value.  Inputs are ASCII (ensure_ascii=True is the
 * serializer's default), so no UTF-8 handling is needed.
 *
 * The element scanner is string-escape and bracket-depth aware: inside
 * an encoded JSON string a quote can only appear escaped, and commas
 * only separate top-level elements at depth 0 outside strings.
 *
 * es_render_hits, further down, writes a hits block with no encoded
 * column at all: ids, and each hit's whole _source where the block
 * returns it, from tables the pack encoded once, scores formatted here to
 * the bytes json.dumps gives.
 */

#include <math.h>
#include <stdint.h>
#include <stdio.h>
#include <stdlib.h>
#include <string.h>

typedef struct {
    const char *p;
    long len;
} span_t;

/* Split a compact JSON array into its top-level element spans.
 * Returns the element count, or -1 on malformed input / overflow. */
static int32_t scan_array(const char *s, span_t *elems, int32_t max_elems)
{
    const char *p = s;
    if (*p != '[')
        return -1;
    p++;
    if (*p == ']')
        return 0;
    int32_t count = 0;
    const char *start = p;
    int depth = 0, in_str = 0, esc = 0;
    for (;; p++) {
        char c = *p;
        if (!c)
            return -1; /* unterminated */
        if (in_str) {
            if (esc)
                esc = 0;
            else if (c == '\\')
                esc = 1;
            else if (c == '"')
                in_str = 0;
            continue;
        }
        if (c == '"') {
            in_str = 1;
        } else if (c == '{' || c == '[') {
            depth++;
        } else if (c == '}') {
            if (--depth < 0)
                return -1;
        } else if (c == ']') {
            if (depth == 0) {
                if (count >= max_elems)
                    return -1;
                elems[count].p = start;
                elems[count].len = p - start;
                return count + 1;
            }
            depth--;
        } else if (c == ',' && depth == 0) {
            if (count >= max_elems)
                return -1;
            elems[count].p = start;
            elems[count].len = p - start;
            count++;
            start = p + 1;
        }
    }
}

#define PUT(str, n)                                   \
    do {                                              \
        long _n = (n);                                \
        if (w + _n > cap) {                           \
            rc = -1;                                  \
            goto done;                                \
        }                                             \
        memcpy(out + w, (str), (size_t)_n);           \
        w += _n;                                      \
    } while (0)

/* Assemble the hits array.
 *   ids_json    compact JSON array of n encoded _id values
 *   scores_json compact JSON array of n encoded _score values
 *   names_json  compact JSON array of encoded _index names (deduped)
 *   name_idx    n indices into names_json's elements
 *   extras_json NULL, or compact JSON array of n objects holding each
 *               hit's residual fields ({} when none)
 * Writes the result into out (capacity cap); returns bytes written,
 * -1 when cap is too small (caller grows and retries), -2 on malformed
 * input (caller uses the Python fallback). */
long es_splice_hits(const char *ids_json, const char *scores_json,
                    const char *names_json, const int32_t *name_idx,
                    const char *extras_json, int32_t n,
                    char *out, long cap)
{
    if (n < 0)
        return -2;
    if (n == 0)
        return cap >= 2 ? (memcpy(out, "[]", 2), 2) : -1;
    long rc = -2;
    long w = 0;
    span_t *ids = malloc(sizeof(span_t) * (size_t)n);
    span_t *scores = malloc(sizeof(span_t) * (size_t)n);
    span_t *names = malloc(sizeof(span_t) * (size_t)n);
    span_t *extras = extras_json ? malloc(sizeof(span_t) * (size_t)n) : NULL;
    int32_t n_names;
    if (!ids || !scores || !names || (extras_json && !extras))
        goto done;
    if (scan_array(ids_json, ids, n) != n)
        goto done;
    if (scan_array(scores_json, scores, n) != n)
        goto done;
    n_names = scan_array(names_json, names, n);
    if (n_names <= 0)
        goto done;
    if (extras_json && scan_array(extras_json, extras, n) != n)
        goto done;
    PUT("[", 1);
    for (int32_t i = 0; i < n; i++) {
        int32_t ni = name_idx[i];
        if (ni < 0 || ni >= n_names) {
            rc = -2;
            goto done;
        }
        if (i)
            PUT(",", 1);
        PUT("{\"_index\":", 10);
        PUT(names[ni].p, names[ni].len);
        PUT(",\"_id\":", 7);
        PUT(ids[i].p, ids[i].len);
        PUT(",\"_score\":", 10);
        PUT(scores[i].p, scores[i].len);
        if (extras && extras[i].len > 2) {
            /* non-empty residual object: splice its inner bytes */
            PUT(",", 1);
            PUT(extras[i].p + 1, extras[i].len - 2);
        }
        PUT("}", 1);
    }
    PUT("]", 1);
    rc = w;
done:
    free(ids);
    free(scores);
    free(names);
    free(extras);
    return rc;
}

/* ------------------------------------------------------------------------
 * es_render_hits — a hits block written straight from the kernel's result
 * columns and the pack's pre-encoded id table (and source table, for a
 * block that returns _source).  No Python object is touched: the caller
 * passes raw pointers and ctypes drops the GIL for the whole call.
 * ---------------------------------------------------------------------- */

/* One probe of the digit search: x printed with p significant digits
 * ("d.ddde+XX") reads back as x. */
static int round_trips(double x, int p, char *buf, size_t cap)
{
    int len = snprintf(buf, cap, "%.*e", p - 1, x);
    if (len <= 0 || (size_t)len >= cap)
        return 0;
    return strtod(buf, NULL) == x;
}

/* buf holds "d.ddde+XX" of p digits: step it to the next p-digit decimal
 * above and say whether that reads back as x. */
static int next_up_round_trips(double x, int p, char *buf, size_t cap)
{
    unsigned long long m = 0, top = 1;
    const char *c = buf;
    for (; *c && *c != 'e'; c++)
        if (*c >= '0' && *c <= '9')
            m = m * 10 + (unsigned long long)(*c - '0');
    if (*c != 'e')
        return 0;
    int e = atoi(c + 1);
    for (int i = 1; i < p; i++)
        top *= 10;
    if (++m == top * 10) {
        m = top;
        e++;
    }
    char d[24];
    if (snprintf(d, sizeof d, "%llu", m) != p)
        return 0;
    int len = p == 1 ? snprintf(buf, cap, "%ce%+03d", d[0], e)
                     : snprintf(buf, cap, "%c.%se%+03d", d[0], d + 1, e);
    if (len <= 0 || (size_t)len >= cap)
        return 0;
    return strtod(buf, NULL) == x;
}

/* Positive finite x → the shortest decimal that reads back as the same
 * double, and of those the nearest, in buf as "d.ddde+XX".  Returns 0 on
 * failure.  A float32 widened to a double nearly always needs 16 or 17
 * digits, so the search starts there; below 15 it bisects, since a
 * shorter decimal that reads back implies every longer one does.  Not so
 * at a power of two, whose lower neighbour is half as far as its upper:
 * there the nearest p-digit decimal can lie below and fail while the
 * next one above reads back, so those are searched upwards, both tried. */
static int shortest_digits(double x, char *buf, size_t cap)
{
    int e2;
    if (frexp(x, &e2) == 0.5) {
        for (int p = 1; p <= 17; p++)
            if (round_trips(x, p, buf, cap)
                || next_up_round_trips(x, p, buf, cap))
                return 1;
        return 0;
    }
    if (!round_trips(x, 16, buf, cap))
        return round_trips(x, 17, buf, cap);
    if (!round_trips(x, 15, buf, cap))
        return round_trips(x, 16, buf, cap);
    int lo = 1, hi = 15; /* hi reads back; find the least p that does */
    while (lo < hi) {
        int mid = (lo + hi) / 2;
        if (round_trips(x, mid, buf, cap))
            hi = mid;
        else
            lo = mid + 1;
    }
    return round_trips(x, hi, buf, cap);
}

#ifdef __SIZEOF_INT128__
typedef unsigned __int128 u128;

/* The same answer as shortest_digits for the scores a ranking gives, in
 * integers and some eight times faster.  f = m x 2^-k with a 24-bit m;
 * as a double its neighbours are 2^-(k+30) away on both sides (m is not
 * a power of two), and every decimal strictly nearer reads back as f.
 * In units of 10^-k / 2^30 the value is X = m x 5^k x 2^30, that
 * half-gap is H = 5^k and the decimals of exponent j are the multiples
 * of U = 10^(j+k) x 2^30: all whole numbers, under 2^127 for k <= 31.
 * The shortest decimal is the nearest multiple of the largest U that
 * has one nearer than H; a multiple of 10 U is one of U, so the search
 * climbs from the largest U <= 2H, which always has one, and stops at
 * the first failure.  Returns 0, for the slow search to decide, outside
 * 13 <= k <= 31 (2^-8 <= f < 2^11), for a power of two, and when a
 * decimal lies exactly on a boundary. */
static int shortest_digits_fast(float f, char *digits, int *nd, int *decpt)
{
    uint32_t bits;
    memcpy(&bits, &f, sizeof bits);
    uint32_t frac = bits & 0x7fffffu;
    int k = 150 - (int)((bits >> 23) & 0xffu);
    if (k < 13 || k > 31 || frac == 0)
        return 0;
    u128 H = 1;
    for (int i = 0; i < k; i++)
        H *= 5;
    u128 X = ((u128)(frac | 0x800000u) * H) << 30;
    u128 U = (u128)1 << 30;
    int t = 0; /* U = 10^t x 2^30 */
    while (U * 10 <= 2 * H) {
        U *= 10;
        t++;
    }
    for (;;) {
        u128 r = X % U;
        u128 d = r < U - r ? r : U - r;
        if (d == H)
            return 0;
        if (d > H) { /* never at the first U: d <= U / 2 <= H there */
            U /= 10;
            t--;
            break;
        }
        if (U > X)
            return 0; /* no such decimal: H < X */
        U *= 10;
        t++;
    }
    u128 q = X / U, r = X % U;
    if (r > U - r || (r == U - r && (q & 1)))
        q++; /* to the nearest, and midway to the even digit */
    unsigned long long D = (unsigned long long)q;
    char tmp[24];
    int n = 0;
    while (D) {
        tmp[n++] = (char)('0' + D % 10);
        D /= 10;
    }
    if (n == 0 || n > 17)
        return 0;
    for (int i = 0; i < n; i++)
        digits[i] = tmp[n - 1 - i];
    *nd = n;
    *decpt = n + t - k;
    return 1;
}
#else
static int shortest_digits_fast(float f, char *digits, int *nd, int *decpt)
{
    (void)f, (void)digits, (void)nd, (void)decpt;
    return 0;
}
#endif

/* f → the bytes json.dumps(float(f)) gives (float.__repr__ of the
 * widened double): shortest digits that round-trip, exponent form when
 * the decimal point falls left of 1e-4 or right of 1e16, ".0" on
 * integers, two exponent digits at least.  out must hold 32 bytes.
 * Returns the length, or 0 for a value this does not format (nan, inf,
 * a locale with another decimal point). */
static int format_score(float f, char *out)
{
    char buf[40];
    char digits[20];
    int nd = 0, decpt = 0, w = 0;
    if (!isfinite(f))
        return 0;
    if (signbit(f)) {
        out[w++] = '-';
        f = -f;
    }
    if (f == 0.0f) {
        memcpy(out + w, "0.0", 3);
        return w + 3;
    }
    if (!shortest_digits_fast(f, digits, &nd, &decpt)) {
        if (!shortest_digits((double)f, buf, sizeof buf))
            return 0;
        const char *p = buf;
        if (*p < '0' || *p > '9')
            return 0;
        digits[nd++] = *p++;
        if (*p == '.') {
            for (p++; *p >= '0' && *p <= '9' && nd < 17; p++)
                digits[nd++] = *p;
        }
        if (*p != 'e')
            return 0; /* a comma from the locale, or too many digits */
        decpt = atoi(p + 1) + 1; /* value = 0.d1d2... x 10^decpt */
    }
    while (nd > 1 && digits[nd - 1] == '0')
        nd--;
    if (decpt <= -4 || decpt > 16) {
        out[w++] = digits[0];
        if (nd > 1) {
            out[w++] = '.';
            memcpy(out + w, digits + 1, (size_t)nd - 1);
            w += nd - 1;
        }
        int e = decpt - 1;
        out[w++] = 'e';
        out[w++] = e < 0 ? '-' : '+';
        if (e < 0)
            e = -e;
        if (e >= 100)
            out[w++] = (char)('0' + e / 100);
        out[w++] = (char)('0' + e / 10 % 10);
        out[w++] = (char)('0' + e % 10);
    } else if (decpt <= 0) {
        out[w++] = '0';
        out[w++] = '.';
        for (int i = decpt; i < 0; i++)
            out[w++] = '0';
        memcpy(out + w, digits, (size_t)nd);
        w += nd;
    } else if (decpt >= nd) {
        memcpy(out + w, digits, (size_t)nd);
        w += nd;
        for (int i = nd; i < decpt; i++)
            out[w++] = '0';
        out[w++] = '.';
        out[w++] = '0';
    } else {
        memcpy(out + w, digits, (size_t)decpt);
        w += decpt;
        out[w++] = '.';
        memcpy(out + w, digits + decpt, (size_t)(nd - decpt));
        w += nd - decpt;
    }
    return w;
}

/* Write [{"_index":<name>,"_id":<id>,"_score":<score>},...] for n hits,
 * each with ,"_source":<source> after its score where src_blob is given.
 *   id_blob, id_off   the pack's ids as json.dumps literals, back to back;
 *                     id i is id_blob[id_off[i] : id_off[i + 1]], n_ids ids
 *   src_blob, src_off NULL, or the docs' stored sources as literals, laid
 *                     out and indexed as the ids are (n_ids of them)
 *   row_offset        n_rows offsets of each pack row's first id
 *   rows, ords        n (pack row, local ordinal) pairs
 *   scores            n float32 scores
 *   name, name_len    the encoded _index literal
 * Returns bytes written, -1 when cap is too small, -2 for a row or ordinal
 * outside the tables, -3 for a score format_score refuses: the caller
 * renders the block in Python for any negative return. */
long es_render_hits(const char *id_blob, const int64_t *id_off, int64_t n_ids,
                    const char *src_blob, const int64_t *src_off,
                    const int64_t *row_offset, int64_t n_rows,
                    const int32_t *rows, const int32_t *ords,
                    const float *scores, int32_t n,
                    const char *name, long name_len, char *out, long cap)
{
    long rc, w = 0;
    char score[32];
    if (n < 0 || name_len < 0)
        return -2;
    PUT("[", 1);
    for (int32_t i = 0; i < n; i++) {
        if (rows[i] < 0 || rows[i] >= n_rows || ords[i] < 0)
            return -2;
        int64_t id = row_offset[rows[i]] + ords[i];
        if (id < 0 || id >= n_ids)
            return -2;
        int slen = format_score(scores[i], score);
        if (slen <= 0)
            return -3;
        if (i)
            PUT(",", 1);
        PUT("{\"_index\":", 10);
        PUT(name, name_len);
        PUT(",\"_id\":", 7);
        PUT(id_blob + id_off[id], id_off[id + 1] - id_off[id]);
        PUT(",\"_score\":", 10);
        PUT(score, slen);
        if (src_blob) {
            PUT(",\"_source\":", 11);
            PUT(src_blob + src_off[id], src_off[id + 1] - src_off[id]);
        }
        PUT("}", 1);
    }
    PUT("]", 1);
    rc = w;
done:
    return rc;
}

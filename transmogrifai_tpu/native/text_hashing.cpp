// Native tokenizer + hashing-trick accumulator for the text vectorizer.
//
// The reference leans on Lucene (JVM) for tokenization and Spark's murmur3
// HashingTF for the hashing trick (OPCollectionHashingVectorizer.scala); our
// host-side equivalent tokenizes ASCII word runs and hashes with zlib's
// CRC-32 — bit-identical to Python's zlib.crc32, so the Python row path and
// this columnar path agree exactly (the OpTransformerSpec parity contract).
// What is not ASCII stays on the Python/regex path (dispatch in hashing.py: a
// column for the dense entry points, a row for hash_tokens_entries).

#include <algorithm>
#include <cstdint>
#include <cstring>
#include <vector>

namespace {

uint32_t crc_table[256];
bool crc_ready = false;

void init_crc() {
    if (crc_ready) return;
    for (uint32_t i = 0; i < 256; ++i) {
        uint32_t c = i;
        for (int k = 0; k < 8; ++k)
            c = (c & 1) ? 0xEDB88320u ^ (c >> 1) : c >> 1;
        crc_table[i] = c;
    }
    crc_ready = true;
}

inline uint32_t crc32_update(uint32_t crc, const unsigned char* p,
                             int64_t len) {
    crc ^= 0xFFFFFFFFu;
    for (int64_t i = 0; i < len; ++i)
        crc = crc_table[(crc ^ p[i]) & 0xFF] ^ (crc >> 8);
    return crc ^ 0xFFFFFFFFu;
}

inline bool is_word(unsigned char c) {
    return (c >= '0' && c <= '9') || (c >= 'a' && c <= 'z') ||
           (c >= 'A' && c <= 'Z');
}

// The ONE tokenizer loop: every entry point routes through this so the
// word-character set, lowercase rule, and 4096-byte token cap cannot drift
// between consumers. emit(row, crc) fires once per token, end_row(row) once
// a row after its last token.
template <class Emit, class EndRow>
inline void scan_tokens(const char* buf, const int64_t* offsets, int64_t n,
                        int32_t lowercase, Emit&& emit, EndRow&& end_row) {
    init_crc();
    unsigned char tok[4096];
    for (int64_t r = 0; r < n; ++r) {
        const char* p = buf + offsets[r];
        const int64_t len = offsets[r + 1] - offsets[r];
        int64_t t = 0;
        for (int64_t i = 0; i <= len; ++i) {
            unsigned char c = (i < len) ? (unsigned char)p[i] : 0;
            if (i < len && is_word(c)) {
                if (t < (int64_t)sizeof(tok))
                    tok[t++] = lowercase && c >= 'A' && c <= 'Z'
                                   ? c + 32 : c;
            } else if (t > 0) {
                emit(r, crc32_update(0u, tok, t));
                t = 0;
            }
        }
        end_row(r);
    }
}

template <class Emit>
inline void scan_tokens(const char* buf, const int64_t* offsets, int64_t n,
                        int32_t lowercase, Emit&& emit) {
    scan_tokens(buf, offsets, n, lowercase, emit, [](int64_t) {});
}

}  // namespace

extern "C" {

// buf: concatenated UTF-8 rows; offsets: [n+1] byte offsets into buf.
// out: float32 [n, stride] row-major; token bins accumulate into
// out[r, col_offset + crc32(token) % num_bins].
void hash_tokens_batch(const char* buf, const int64_t* offsets, int64_t n,
                       int32_t num_bins, int32_t lowercase,
                       int32_t binary_freq, float* out, int64_t stride,
                       int64_t col_offset) {
    scan_tokens(buf, offsets, n, lowercase,
                [&](int64_t r, uint32_t h) {
                    float* row = out + r * stride + col_offset;
                    int64_t b = (int64_t)(h % (uint32_t)num_bins);
                    if (binary_freq) row[b] = 1.0f;
                    else row[b] += 1.0f;
                });
}

// Accumulates every row's token bins into ONE histogram hist[num_bins]
// (double counts) — the RawFeatureFilter distribution pass, which needs the
// corpus-level token distribution rather than per-row vectors, so no
// [n, bins] intermediate is materialized.
void hash_tokens_hist(const char* buf, const int64_t* offsets, int64_t n,
                      int32_t num_bins, int32_t lowercase, double* hist) {
    scan_tokens(buf, offsets, n, lowercase,
                [&](int64_t, uint32_t h) {
                    hist[h % (uint32_t)num_bins] += 1.0;
                });
}

// A column's entries that are not zero, in row order: row r owns
// row_entries[r] (slot, count) pairs, its distinct slots ascending, written
// one row after another into slot_out / count_out (capacity cap: a row of
// L bytes holds at most (L + 1) / 2 tokens, and no more distinct slots than
// num_bins). No [n, bins] block is made. Returns the pairs written and
// leaves the column's token count in *tokens_out; -1 if cap was too small.
int64_t hash_tokens_entries(const char* buf, const int64_t* offsets,
                            int64_t n, int32_t num_bins, int32_t lowercase,
                            int32_t* row_entries, int32_t* slot_out,
                            int32_t* count_out, int64_t cap,
                            int64_t* tokens_out) {
    std::vector<int32_t> counts((size_t)num_bins, 0);
    std::vector<int32_t> touched;
    touched.reserve(256);
    int64_t written = 0, tokens = 0;
    bool overflow = false;
    scan_tokens(
        buf, offsets, n, lowercase,
        [&](int64_t, uint32_t h) {
            int32_t b = (int32_t)(h % (uint32_t)num_bins);
            if (counts[b]++ == 0) touched.push_back(b);
            ++tokens;
        },
        [&](int64_t r) {
            const int64_t k = (int64_t)touched.size();
            row_entries[r] = (int32_t)k;
            if (overflow || written + k > cap) {
                overflow = true;
            } else {
                std::sort(touched.begin(), touched.end());
                for (int32_t b : touched) {
                    slot_out[written] = b;
                    count_out[written++] = counts[b];
                }
            }
            for (int32_t b : touched) counts[b] = 0;
            touched.clear();
        });
    *tokens_out = tokens;
    return overflow ? -1 : written;
}

}  // extern "C"

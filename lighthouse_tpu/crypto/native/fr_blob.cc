// Barycentric evaluation of a blob's polynomial over Fr, the BLS12-381
// scalar field — the host's field work of a KZG blob batch (crypto/kzg.py).
//
// One stateless function. Field elements are 4 x 64-bit limbs, little-endian,
// in Montgomery form (radix 2^256); bytes on the boundary are 32-byte
// big-endian, as the consensus spec serializes them. Every constant but the
// modulus is derived from it at call time (a few microseconds), so there is
// no table to get wrong and no state to share between threads.
//
// Build: g++ -O2 -std=c++17 -shared -fPIC (utils/native_build.py).

#include <cstdint>
#include <cstring>
#include <vector>

namespace {

typedef unsigned __int128 u128;

struct Fr { uint64_t l[4]; };

// r = 0x73eda753299d7d483339d80809a1d80553bda402fffe5bfeffffffff00000001
const Fr MOD = {{0xffffffff00000001ULL, 0x53bda402fffe5bfeULL,
                 0x3339d80809a1d805ULL, 0x73eda753299d7d48ULL}};

inline bool geq(const Fr& a, const Fr& b) {
    for (int i = 3; i >= 0; --i) {
        if (a.l[i] != b.l[i]) return a.l[i] > b.l[i];
    }
    return true;
}

inline bool is_zero(const Fr& a) {
    return (a.l[0] | a.l[1] | a.l[2] | a.l[3]) == 0;
}

inline void sub_raw(Fr& a, const Fr& b) {          // a -= b, a >= b
    u128 borrow = 0;
    for (int i = 0; i < 4; ++i) {
        u128 d = (u128)a.l[i] - b.l[i] - borrow;
        a.l[i] = (uint64_t)d;
        borrow = (d >> 64) & 1;
    }
}

inline Fr add(const Fr& a, const Fr& b) {           // a, b < r < 2^255
    Fr out;
    u128 carry = 0;
    for (int i = 0; i < 4; ++i) {
        u128 s = (u128)a.l[i] + b.l[i] + carry;
        out.l[i] = (uint64_t)s;
        carry = s >> 64;
    }
    if (geq(out, MOD)) sub_raw(out, MOD);
    return out;
}

inline Fr sub(const Fr& a, const Fr& b) {
    Fr out = a;
    if (!geq(a, b)) {                               // a + r - b, no overflow
        u128 carry = 0;
        for (int i = 0; i < 4; ++i) {
            u128 s = (u128)out.l[i] + MOD.l[i] + carry;
            out.l[i] = (uint64_t)s;
            carry = s >> 64;
        }
    }
    sub_raw(out, b);
    return out;
}

// Montgomery product a * b / 2^256 mod r (CIOS), inv = -r^-1 mod 2^64
inline Fr mul(const Fr& a, const Fr& b, uint64_t inv) {
    uint64_t t[6] = {0, 0, 0, 0, 0, 0};
    for (int i = 0; i < 4; ++i) {
        u128 carry = 0;
        for (int j = 0; j < 4; ++j) {
            u128 s = (u128)a.l[j] * b.l[i] + t[j] + carry;
            t[j] = (uint64_t)s;
            carry = s >> 64;
        }
        u128 s = (u128)t[4] + carry;
        t[4] = (uint64_t)s;
        t[5] = (uint64_t)(s >> 64);
        uint64_t m = t[0] * inv;
        carry = ((u128)m * MOD.l[0] + t[0]) >> 64;
        for (int j = 1; j < 4; ++j) {
            u128 s2 = (u128)m * MOD.l[j] + t[j] + carry;
            t[j - 1] = (uint64_t)s2;
            carry = s2 >> 64;
        }
        s = (u128)t[4] + carry;
        t[3] = (uint64_t)s;
        t[4] = t[5] + (uint64_t)(s >> 64);
    }
    Fr out = {{t[0], t[1], t[2], t[3]}};
    if (t[4] || geq(out, MOD)) sub_raw(out, MOD);
    return out;
}

inline Fr from_be(const uint8_t* p) {
    Fr out;
    for (int i = 0; i < 4; ++i) {
        uint64_t v = 0;
        for (int k = 0; k < 8; ++k) v = (v << 8) | p[8 * (3 - i) + k];
        out.l[i] = v;
    }
    return out;
}

inline void to_be(const Fr& a, uint8_t* p) {
    for (int i = 0; i < 4; ++i) {
        uint64_t v = a.l[i];
        for (int k = 7; k >= 0; --k) { p[8 * (3 - i) + k] = (uint8_t)v; v >>= 8; }
    }
}

}  // namespace

extern "C" {

// y = p(z) for the polynomial whose evaluations over `roots` (n 32-byte
// big-endian field elements, the domain in the blob's own order) are the
// blob's n field elements. Returns 0 and writes y (32 bytes, big-endian);
// 1 if a field element of the blob or z is not canonical (>= r).
//
// p(z) = (z^n - 1)/n * sum_i p_i w_i / (z - w_i), and prod_i (z - w_i) is
// z^n - 1 over the whole domain, so with the sum kept as one fraction
// N / D (N <- N b_i + p_i w_i D, D <- D b_i, b_i = z - w_i) the answer is
// N / n: no inversion but the constant's. z on the domain gives p_i itself.
int fr_blob_evaluate(const uint8_t* blob, uint64_t n, const uint8_t* roots,
                     const uint8_t* z_be, uint8_t* y_be) {
    uint64_t inv = 1;                               // -r^-1 mod 2^64, by Newton
    for (int i = 0; i < 6; ++i) inv *= 2 - MOD.l[0] * inv;
    inv = ~inv + 1;
    Fr one = {{1, 0, 0, 0}};
    Fr r1 = one;                                    // 2^256 mod r
    for (int i = 0; i < 256; ++i) r1 = add(r1, r1);
    Fr r2 = r1;                                     // 2^512 mod r
    for (int i = 0; i < 256; ++i) r2 = add(r2, r2);

    Fr z = from_be(z_be);
    if (geq(z, MOD)) return 1;
    z = mul(z, r2, inv);
    std::vector<Fr> p(n);
    for (uint64_t i = 0; i < n; ++i) {
        p[i] = from_be(blob + 32 * i);
        if (geq(p[i], MOD)) return 1;
    }
    // N stays in standard form, D and b in Montgomery form: a Montgomery
    // product of one of each is in standard form again
    Fr num = {{0, 0, 0, 0}}, den = r1;
    for (uint64_t i = 0; i < n; ++i) {
        Fr w = mul(from_be(roots + 32 * i), r2, inv);
        Fr b = sub(z, w);
        if (is_zero(b)) {                           // z on the domain
            to_be(p[i], y_be);
            return 0;
        }
        Fr pw = mul(p[i], w, inv);
        num = add(mul(num, b, inv), mul(pw, den, inv));
        den = mul(den, b, inv);
    }
    // 1 / n = n^(r - 2), n in Montgomery form
    Fr nn = {{n, 0, 0, 0}};
    nn = mul(nn, r2, inv);
    Fr e = MOD;
    e.l[0] -= 2;                                    // r - 2: no borrow
    Fr acc = r1;
    for (int bit = 254; bit >= 0; --bit) {
        acc = mul(acc, acc, inv);
        if ((e.l[bit / 64] >> (bit % 64)) & 1) acc = mul(acc, nn, inv);
    }
    to_be(mul(num, acc, inv), y_be);
    return 0;
}

}  // extern "C"

"""The plain reference of `mainnet-electra-block-8`: batch verification of
signature sets whose signers are named by their 48-byte compressed public
keys, as the registry stores them.

A whole BLS12-381 of its own in Python integers, written from the
definitions and importing nothing of the program under test: the fields
(Fp, Fp2 = Fp[u]/(u^2 + 1), Fp12 = Fp2[w]/(w^6 - (1 + u))), both curves in
affine coordinates, a key's decompression (x, the sign bit, a square root),
hash-to-G2 (RFC 9380 BLS12381G2_XMD:SHA-256_SSWU_RO_: expand_message_xmd,
simplified SWU on the 3-isogenous curve, the isogeny, the cofactor cleared
by ONE multiplication by h_eff) and the pairing (the Miller loop of the
optimal ate pairing with lines through affine points of the twist, and the
final exponentiation as ONE power (p^12 - 1) / r). No JAX, no table on any
device, no limbs, no index grid, no `PublicKey` cache, no Frobenius
constant, no Montgomery form. Slow and short on purpose: ~0.6 s a final
exponentiation, ~0.15 ms a key's square root.

The verdict is the spec's,

    e(-G1, sum_i z_i sig_i) * prod_i e(z_i sum_k pk_ik, H(m_i)) == 1

with 64-bit nonzero coefficients z_i (consensus-specs
`specs/phase0/beacon-chain.md` `bls.FastAggregateVerify`, batched as
Lighthouse's `verify_signature_sets` batches it, blst.rs:40-120). What it
does not do: the subgroup check of a registry key, which the deposit that
brought the key paid once (`KeyValidate`); the curve check it does (the
square root exists). A signature arrives as an affine G2 point.
"""

from __future__ import annotations

import hashlib

# ------------------------------------------------------------- constants
# draft-irtf-cfrg-pairing-friendly-curves, BLS12-381

P = 0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFAAAB
R = 0x73EDA753299D7D483339D80809A1D80553BDA402FFFE5BFEFFFFFFFF00000001
#: the curve's parameter is -X_ABS; r = x^4 - x^2 + 1
X_ABS = 0xD201000000010000

G1 = (
    0x17F1D3A73197D7942695638C4FA9AC0FC3688C4F9774B905A14E3A3F171BAC586C55E83FF97A1AEFFB3AF00ADB22C6BB,
    0x08B3F481E3AAA0F1A09E30ED741D8AE4FCF5E095D5D00AF600DB18CB2C04B3EDD03CC744A2888AE40CAA232946C5E7E1,
)

#: RFC 9380 8.8.2: the multiplication that clears G2's cofactor
H_EFF = 0xBC69F08F2EE75B3584C6A0EA91B352888E2A8E9145AD7689986FF031508FFE1329C2F178731DB956D82BF015D1212B02EC0EC69D7477C1AE954CBC06689F6A359894C0ADEBBF6B4E8020005AAA95551
#: the Ethereum ciphersuite (proof of possession)
DST = b"BLS_SIG_BLS12381G2_XMD:SHA-256_SSWU_RO_POP_"

#: the final exponentiation, whole
FINAL_EXPONENT = (P ** 12 - 1) // R

# ------------------------------------------------------------------- Fp2
# (a, b) = a + b u, u^2 = -1


def f2_add(a, b):
    return (a[0] + b[0]) % P, (a[1] + b[1]) % P


def f2_sub(a, b):
    return (a[0] - b[0]) % P, (a[1] - b[1]) % P


def f2_neg(a):
    return -a[0] % P, -a[1] % P


def f2_mul(a, b):
    t0, t1 = a[0] * b[0], a[1] * b[1]
    return (t0 - t1) % P, ((a[0] + a[1]) * (b[0] + b[1]) - t0 - t1) % P


def f2_sqr(a):
    return (a[0] + a[1]) * (a[0] - a[1]) % P, 2 * a[0] * a[1] % P


def f2_scale(a, k: int):
    return a[0] * k % P, a[1] * k % P


def f2_inv(a):
    n = pow(a[0] * a[0] + a[1] * a[1], -1, P)
    return a[0] * n % P, -a[1] * n % P


def fp_sqrt(a: int):
    """A square root of a in Fp (p = 3 mod 4), or None."""
    a %= P
    root = pow(a, (P + 1) // 4, P)
    return root if root * root % P == a else None


def f2_is_square(a) -> bool:
    """a is a square in Fp2 exactly when its norm is one in Fp."""
    norm = (a[0] * a[0] + a[1] * a[1]) % P
    return norm == 0 or pow(norm, (P - 1) // 2, P) == 1


def f2_sqrt(a):
    """A square root of a in Fp2, or None: with s^2 = norm(a), the root is
    x0 + x1 u where x0^2 = (a0 +- s) / 2 and x1 = a1 / (2 x0)."""
    a0, a1 = a[0] % P, a[1] % P
    if a1 == 0:
        root = fp_sqrt(a0)
        if root is not None:
            return root, 0
        return 0, fp_sqrt(-a0)          # -1 is no square: one of the two is
    s = fp_sqrt(a0 * a0 + a1 * a1)
    if s is None:
        return None
    half = (P + 1) // 2
    x0 = fp_sqrt((a0 + s) * half)
    if x0 is None:
        x0 = fp_sqrt((a0 - s) * half)
    x1 = a1 * pow(2 * x0, -1, P) % P
    return (x0, x1) if f2_sqr((x0, x1)) == (a0, a1) else None


def f2_sgn0(a) -> int:
    """RFC 9380 4.1, m = 2."""
    return a[0] % 2 or (a[0] == 0 and a[1] % 2)


# ---------------------------------------------------------------- curves
# affine points, None the point at infinity. E1: y^2 = x^3 + 4 over Fp;
# E2: y^2 = x^3 + 4 (1 + u) over Fp2 (the twist the pairing reads).


def g1_add(p, q):
    if p is None:
        return q
    if q is None:
        return p
    (x1, y1), (x2, y2) = p, q
    if x1 == x2:
        if (y1 + y2) % P == 0:
            return None
        lam = 3 * x1 * x1 * pow(2 * y1, -1, P) % P
    else:
        lam = (y2 - y1) * pow(x2 - x1, -1, P) % P
    x3 = (lam * lam - x1 - x2) % P
    return x3, (lam * (x1 - x3) - y1) % P


def g1_neg(p):
    return None if p is None else (p[0], -p[1] % P)


def g1_mul(p, k: int):
    acc = None
    while k:
        if k & 1:
            acc = g1_add(acc, p)
        p = g1_add(p, p)
        k >>= 1
    return acc


def _g2_slope(p, q):
    """The slope of the line through p and q (the tangent where p = q),
    or None where that line is vertical."""
    (x1, y1), (x2, y2) = p, q
    if x1 == x2:
        if f2_add(y1, y2) == (0, 0):
            return None
        return f2_mul(f2_scale(f2_sqr(x1), 3), f2_inv(f2_scale(y1, 2)))
    return f2_mul(f2_sub(y2, y1), f2_inv(f2_sub(x2, x1)))


def _g2_chord(p, q, lam):
    x3 = f2_sub(f2_sub(f2_sqr(lam), p[0]), q[0])
    return x3, f2_sub(f2_mul(lam, f2_sub(p[0], x3)), p[1])


def g2_add(p, q):
    if p is None:
        return q
    if q is None:
        return p
    lam = _g2_slope(p, q)
    return None if lam is None else _g2_chord(p, q, lam)


def g2_mul(p, k: int):
    acc = None
    while k:
        if k & 1:
            acc = g2_add(acc, p)
        p = g2_add(p, p)
        k >>= 1
    return acc


def g2_on_curve(p) -> bool:
    x, y = p
    return f2_sqr(y) == f2_add(f2_mul(f2_sqr(x), x), (4, 4))


# ----------------------------------------------------- the registry's keys


def decompress_key(key_bytes: bytes) -> tuple:
    """(x, y) of a 48-byte compressed G1 key (the ZCash form: bit 7 set,
    bit 6 the point at infinity, bit 5 the larger of the two y); raises
    for a key that is no point of the curve."""
    key_bytes = bytes(key_bytes)
    if len(key_bytes) != 48 or not key_bytes[0] & 0x80:
        raise ValueError("a public key is 48 bytes in compressed form")
    if key_bytes[0] & 0x40:
        raise ValueError("a public key may not be the point at infinity")
    x = int.from_bytes(key_bytes, "big") & ((1 << 381) - 1)
    if x >= P:
        raise ValueError("x is no field element")
    y = fp_sqrt(x * x * x + 4)
    if y is None:
        raise ValueError("x is on no point of the curve")
    if (y > (P - 1) // 2) != bool(key_bytes[0] & 0x20):
        y = P - y
    return x, y


def compress_key(point) -> bytes:
    """The 48 bytes of an affine G1 key: what `decompress_key` reads."""
    x, y = point
    return (x | (4 + (y > (P - 1) // 2)) << 381).to_bytes(48, "big")


def keys_not_of(key_bytes_list, points) -> int:
    """How many of `points` are NOT what `key_bytes_list` compress: the x
    is the bytes', the point lies on the curve, the sign bit names this y.
    No square root, so a whole registry takes seconds."""
    wrong = 0
    low = (1 << 381) - 1
    for key_bytes, (x, y) in zip(key_bytes_list, points, strict=True):
        word = int.from_bytes(key_bytes, "big")
        wrong += not (len(key_bytes) == 48 and word >> 381 == 4 + (y > (P - 1) // 2)
                      and word & low == x and 0 <= y < P
                      and (y * y - x * x * x - 4) % P == 0)
    return wrong


def sum_keys(points) -> tuple | None:
    """The sum of affine G1 points, in Jacobian coordinates with one
    inversion at the end; None is the point at infinity."""
    X = Y = Z = 0
    for x2, y2 in points:
        if Z == 0:
            X, Y, Z = x2, y2, 1
            continue
        zz = Z * Z % P
        h = (x2 * zz - X) % P
        r = (y2 * zz % P * Z - Y) % P
        if h == 0:
            # the same x: the point itself (double it) or its negative
            at = g1_add(_affine(X, Y, Z), (x2, y2))
            X, Y, Z = (0, 0, 0) if at is None else (at[0], at[1], 1)
            continue
        hh = h * h % P
        hhh = hh * h % P
        v = X * hh % P
        X3 = (r * r - hhh - 2 * v) % P
        Y = (r * (v - X3) - Y * hhh) % P
        X, Z = X3, Z * h % P
    return None if Z == 0 else _affine(X, Y, Z)


def _affine(X: int, Y: int, Z: int) -> tuple:
    zi = pow(Z, -1, P)
    zi2 = zi * zi % P
    return X * zi2 % P, Y * zi2 % P * zi % P


# ------------------------------------------------------------ hash to G2
# RFC 9380: 5.3.1 expand_message_xmd, 5.2 hash_to_field, 6.6.2 the
# simplified SWU map (straight-line form of 6.6.2's definition), E.3 the
# 3-isogeny, 8.8.2 the suite

#: the isogenous curve E2': y^2 = x^3 + A x + B, and the map's Z
SSWU_A = (0, 240)
SSWU_B = (1012, 1012)
SSWU_Z = (P - 2, P - 1)

#: E.3, lowest degree first; the denominators are monic
ISO_X_NUM = (
    (0x5C759507E8E333EBB5B7A9A47D7ED8532C52D39FD3A042A88B58423C50AE15D5C2638E343D9C71C6238AAAAAAAA97D6,
     0x5C759507E8E333EBB5B7A9A47D7ED8532C52D39FD3A042A88B58423C50AE15D5C2638E343D9C71C6238AAAAAAAA97D6),
    (0,
     0x11560BF17BAA99BC32126FCED787C88F984F87ADF7AE0C7F9A208C6B4F20A4181472AAA9CB8D555526A9FFFFFFFFC71A),
    (0x11560BF17BAA99BC32126FCED787C88F984F87ADF7AE0C7F9A208C6B4F20A4181472AAA9CB8D555526A9FFFFFFFFC71E,
     0x8AB05F8BDD54CDE190937E76BC3E447CC27C3D6FBD7063FCD104635A790520C0A395554E5C6AAAA9354FFFFFFFFE38D),
    (0x171D6541FA38CCFAED6DEA691F5FB614CB14B4E7F4E810AA22D6108F142B85757098E38D0F671C7188E2AAAAAAAA5ED1,
     0),
)
ISO_X_DEN = ((0, P - 72), (12, P - 12), (1, 0))
ISO_Y_NUM = (
    (0x1530477C7AB4113B59A4C18B076D11930F7DA5D4A07F649BF54439D87D27E500FC8C25EBF8C92F6812CFC71C71C6D706,
     0x1530477C7AB4113B59A4C18B076D11930F7DA5D4A07F649BF54439D87D27E500FC8C25EBF8C92F6812CFC71C71C6D706),
    (0,
     0x5C759507E8E333EBB5B7A9A47D7ED8532C52D39FD3A042A88B58423C50AE15D5C2638E343D9C71C6238AAAAAAAA97BE),
    (0x11560BF17BAA99BC32126FCED787C88F984F87ADF7AE0C7F9A208C6B4F20A4181472AAA9CB8D555526A9FFFFFFFFC71C,
     0x8AB05F8BDD54CDE190937E76BC3E447CC27C3D6FBD7063FCD104635A790520C0A395554E5C6AAAA9354FFFFFFFFE38F),
    (0x124C9AD43B6CF79BFBF7043DE3811AD0761B0F37A1E26286B0E977C69AA274524E79097A56DC4BD9E1B371C71C718B10,
     0),
)
ISO_Y_DEN = ((P - 432, P - 432), (0, P - 216), (18, P - 18), (1, 0))


def expand_message_xmd(message: bytes, dst: bytes, n: int) -> bytes:
    dst_prime = dst + bytes([len(dst)])
    b0 = hashlib.sha256(bytes(64) + message + n.to_bytes(2, "big") + b"\x00"
                        + dst_prime).digest()
    blocks = [hashlib.sha256(b0 + b"\x01" + dst_prime).digest()]
    for i in range(2, -(-n // 32) + 1):
        mixed = bytes(a ^ b for a, b in zip(b0, blocks[-1]))
        blocks.append(hashlib.sha256(mixed + bytes([i]) + dst_prime).digest())
    return b"".join(blocks)[:n]


def hash_to_field(message: bytes, dst: bytes) -> list:
    """Two elements of Fp2 (count = 2, m = 2, L = 64)."""
    raw = expand_message_xmd(message, dst, 256)
    words = [int.from_bytes(raw[at:at + 64], "big") % P
             for at in range(0, 256, 64)]
    return [(words[0], words[1]), (words[2], words[3])]


def _on_iso(x):
    """x^3 + A x + B of the isogenous curve."""
    return f2_add(f2_mul(f2_add(f2_sqr(x), SSWU_A), x), SSWU_B)


def sswu(u) -> tuple:
    """u of Fp2 to a point of E2' (RFC 9380 6.6.2)."""
    zu2 = f2_mul(SSWU_Z, f2_sqr(u))
    tv1 = f2_add(f2_sqr(zu2), zu2)
    if tv1 == (0, 0):
        x1 = f2_mul(SSWU_B, f2_inv(f2_mul(SSWU_Z, SSWU_A)))
    else:
        x1 = f2_mul(f2_mul(f2_neg(SSWU_B), f2_inv(SSWU_A)),
                    f2_add((1, 0), f2_inv(tv1)))
    gx1 = _on_iso(x1)
    if f2_is_square(gx1):
        x, y = x1, f2_sqrt(gx1)
    else:
        x = f2_mul(zu2, x1)
        y = f2_sqrt(_on_iso(x))
    if f2_sgn0(u) != f2_sgn0(y):
        y = f2_neg(y)
    return x, y


def _horner(coefficients, x):
    acc = coefficients[-1]
    for c in reversed(coefficients[:-1]):
        acc = f2_add(f2_mul(acc, x), c)
    return acc


def iso_map(point):
    """E2' to E2: the 3-isogeny of RFC 9380 E.3."""
    x, y = point
    x_den, y_den = _horner(ISO_X_DEN, x), _horner(ISO_Y_DEN, x)
    if x_den == (0, 0) or y_den == (0, 0):
        return None                                   # the isogeny's kernel
    return (f2_mul(_horner(ISO_X_NUM, x), f2_inv(x_den)),
            f2_mul(y, f2_mul(_horner(ISO_Y_NUM, x), f2_inv(y_den))))


def hash_to_g2(message: bytes, dst: bytes = DST):
    u0, u1 = hash_to_field(bytes(message), dst)
    return g2_mul(g2_add(iso_map(sswu(u0)), iso_map(sswu(u1))), H_EFF)


# ----------------------------------------------------------- the pairing
# Fp12 = Fp2[w] / (w^6 - (1 + u)): a list of six Fp2 coefficients, lowest
# power of w first. The untwist is (x', y') -> (x' / w^2, y' / w^3), so the
# line of slope s through the twist's point T, evaluated at (xP, yP) of E1
# and multiplied by w^3 (an element of Fp4, which the final exponentiation
# kills, as it kills the vertical lines left out here), is
#     (s xT - yT)  -  s xP w^2  +  yP w^3

F12_ONE = [(1, 0)] + [(0, 0)] * 5


def f12_mul(a, b):
    re, im = [0] * 11, [0] * 11
    for i, (a0, a1) in enumerate(a):
        if a0 == 0 == a1:
            continue
        for j, (b0, b1) in enumerate(b):
            re[i + j] += a0 * b0 - a1 * b1
            im[i + j] += a0 * b1 + a1 * b0
    # w^6 = 1 + u: (x + y u)(1 + u) = (x - y) + (x + y) u
    return [((re[k] + re[k + 6] - im[k + 6]) % P,
             (im[k] + re[k + 6] + im[k + 6]) % P) if k < 5
            else (re[5] % P, im[5] % P) for k in range(6)]


def f12_pow(a, e: int):
    acc = None
    for bit in bin(e)[2:]:
        if acc is not None:
            acc = f12_mul(acc, acc)
        if bit == "1":
            acc = a if acc is None else f12_mul(acc, a)
    return acc


def _line(t, lam, p):
    return [f2_sub(f2_mul(lam, t[0]), t[1]), (0, 0),
            f2_scale(lam, -p[0] % P), (p[1], 0), (0, 0), (0, 0)]


def miller_loop(p, q):
    """f_{|x|, q}(p) for p of E1, q of the twist, both affine and of order
    r. The parameter's sign is left out: it inverts the value, and a
    product of values is one exactly when the product of inverses is."""
    f, t = F12_ONE, q
    for bit in bin(X_ABS)[3:]:
        lam = _g2_slope(t, t)
        f = f12_mul(f12_mul(f, f), _line(t, lam, p))
        t = _g2_chord(t, t, lam)
        if bit == "1":
            lam = _g2_slope(t, q)
            f = f12_mul(f, _line(t, lam, p))
            t = _g2_chord(t, q, lam)
    return f


def pairing_product_is_one(pairs) -> bool:
    """prod e(p_i, q_i) == 1 over [(point of E1, point of the twist)]; a
    pair with the point at infinity on either side contributes 1."""
    f = F12_ONE
    for p, q in pairs:
        if p is not None and q is not None:
            f = f12_mul(f, miller_loop(p, q))
    return f12_pow(f, FINAL_EXPONENT) == F12_ONE


# ---------------------------------------------------------- the verdict


def verify_signature_sets(sets, coefficients, decompressed=None) -> bool:
    """`sets`: [(signature as an affine G2 point, [48-byte keys], 32-byte
    message), ...]; `coefficients`: one nonzero 64-bit integer a set.
    `decompressed`: a dict the caller may hand to several calls, so that a
    key's square root is taken once ({key bytes: point}, filled here)."""
    if decompressed is None:
        decompressed = {}
    if not sets or len(sets) != len(coefficients):
        raise ValueError("one coefficient a set, at least one set")
    pairs = []
    sig_acc = None
    for (sig, key_bytes, message), z in zip(sets, coefficients):
        if not 0 < z < 1 << 64:
            raise ValueError("coefficients are nonzero and 64-bit")
        if sig is None or not key_bytes:
            return False
        for b in key_bytes:
            if b not in decompressed:
                decompressed[b] = decompress_key(b)
        agg = sum_keys([decompressed[b] for b in key_bytes])
        if agg is None:
            return False
        pairs.append((g1_mul(agg, z), hash_to_g2(message)))
        sig_acc = g2_add(sig_acc, g2_mul(sig, z))
    pairs.append((g1_neg(G1), sig_acc))
    return pairing_product_is_one(pairs)


def registry_digest(points) -> str:
    """SHA-256 over the registry's keys in index order, each x then y as
    48-byte little-endian integers: what a table of those keys must hash
    to (`PubkeyTable.digest`)."""
    h = hashlib.sha256()
    for x, y in points:
        h.update(x.to_bytes(48, "little"))
        h.update(y.to_bytes(48, "little"))
    return h.hexdigest()

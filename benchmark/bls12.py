"""BLS12-381 for the benchmark: keys, signatures and the plain reference.

The benchmark's own arithmetic, written from the public specifications
and independent of the system under test:

- the curve and its parameters (draft-irtf-cfrg-pairing-friendly-curves,
  BLS12-381), compressed point encoding (the ZCash format the Ethereum
  consensus specs use);
- hash_to_curve per RFC 9380, suite BLS12381G2_XMD:SHA-256_SSWU_RO_:
  expand_message_xmd, simplified SWU onto the 3-isogenous curve E2',
  the 3-isogeny to E2 (derived from Velu's formulas below) and cofactor
  clearing by the endomorphism method of RFC 9380 appendix G.3;
- signing per the Ethereum ciphersuite BLS_SIG_BLS12381G2_XMD:SHA-256_SSWU_RO_POP_
  (signature = sk * H(m) in G2, public key = sk * g1 in G1).

Field elements are Python ints; Fp2 elements are pairs (a0, a1) for
a0 + a1*u with u^2 = -1. Points are Jacobian triples, Z = 0 at infinity.
Nothing here is constant-time: it makes test traffic, not keys anyone
uses. Importing this module imports nothing of the system under test
and starts nothing.
"""

from __future__ import annotations

import hashlib

P = 0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFAAAB
R = 0x73EDA753299D7D483339D80809A1D80553BDA402FFFE5BFEFFFFFFFF00000001
X_ABS = 0xD201000000010000  # |x|; the BLS parameter x is negative
HALF_P = (P - 1) // 2

G1_X = 0x17F1D3A73197D7942695638C4FA9AC0FC3688C4F9774B905A14E3A3F171BAC586C55E83FF97A1AEFFB3AF00ADB22C6BB
G1_Y = 0x08B3F481E3AAA0F1A09E30ED741D8AE4FCF5E095D5D00AF600DB18CB2C04B3EDD03CC744A2888AE40CAA232946C5E7E1

DST_G2 = b"BLS_SIG_BLS12381G2_XMD:SHA-256_SSWU_RO_POP_"

# -- Fp2 ----------------------------------------------------------------------

F2_ZERO = (0, 0)
F2_ONE = (1, 0)


def f2_add(a, b):
    return ((a[0] + b[0]) % P, (a[1] + b[1]) % P)


def f2_sub(a, b):
    return ((a[0] - b[0]) % P, (a[1] - b[1]) % P)


def f2_neg(a):
    return (-a[0] % P, -a[1] % P)


def f2_mul(a, b):
    a0, a1 = a
    b0, b1 = b
    t0 = a0 * b0
    t1 = a1 * b1
    return ((t0 - t1) % P, ((a0 + a1) * (b0 + b1) - t0 - t1) % P)


def f2_sqr(a):
    a0, a1 = a
    return ((a0 + a1) * (a0 - a1) % P, 2 * a0 * a1 % P)


def f2_scale(a, k: int):
    return (a[0] * k % P, a[1] * k % P)


def f2_conj(a):
    return (a[0], -a[1] % P)


def f2_inv(a):
    a0, a1 = a
    n = pow((a0 * a0 + a1 * a1) % P, P - 2, P)
    return (a0 * n % P, -a1 * n % P)


def f2_pow(a, e: int):
    out = F2_ONE
    for bit in bin(e)[2:]:
        out = f2_sqr(out)
        if bit == "1":
            out = f2_mul(out, a)
    return out


def _fp_sqrt(v: int):
    """Square root in Fp (p = 3 mod 4), or None."""
    r = pow(v, (P + 1) // 4, P)
    return r if r * r % P == v % P else None


def f2_sqrt(a):
    """A square root in Fp2, or None: through the norm, so that every
    exponentiation is one in Fp."""
    a0, a1 = a
    if a1 == 0:
        r = _fp_sqrt(a0)
        if r is not None:
            return (r, 0)
        r = _fp_sqrt(-a0 % P)
        return None if r is None else (0, r)
    s = _fp_sqrt((a0 * a0 + a1 * a1) % P)
    if s is None:
        return None
    inv2 = (P + 1) // 2
    x0 = _fp_sqrt((a0 + s) * inv2 % P)
    if x0 is None:
        x0 = _fp_sqrt((a0 - s) * inv2 % P)
        if x0 is None:
            return None
    x1 = a1 * pow(2 * x0, P - 2, P) % P
    out = (x0, x1)
    return out if f2_sqr(out) == (a0 % P, a1 % P) else None


def f2_sgn0(a) -> int:
    """RFC 9380 sgn0 for m = 2."""
    return (a[0] & 1) | ((a[0] == 0) & (a[1] & 1))


# -- G1 (Jacobian over Fp, y^2 = x^3 + 4) -------------------------------------

G1_INF = (1, 1, 0)


def g1_dbl(p):
    x, y, z = p
    if z == 0:
        return p
    a = x * x % P
    b = y * y % P
    c = b * b % P
    d = 2 * ((x + b) * (x + b) - a - c) % P
    e = 3 * a % P
    x3 = (e * e - 2 * d) % P
    return (x3, (e * (d - x3) - 8 * c) % P, 2 * y * z % P)


def g1_add(p, q):
    x1, y1, z1 = p
    x2, y2, z2 = q
    if z1 == 0:
        return q
    if z2 == 0:
        return p
    z1z1 = z1 * z1 % P
    z2z2 = z2 * z2 % P
    u1 = x1 * z2z2 % P
    u2 = x2 * z1z1 % P
    s1 = y1 * z2 * z2z2 % P
    s2 = y2 * z1 * z1z1 % P
    h = (u2 - u1) % P
    r = 2 * (s2 - s1) % P
    if h == 0:
        return g1_dbl(p) if r == 0 else G1_INF
    i = 4 * h * h % P
    j = h * i % P
    v = u1 * i % P
    x3 = (r * r - j - 2 * v) % P
    y3 = (r * (v - x3) - 2 * s1 * j) % P
    z3 = ((z1 + z2) * (z1 + z2) - z1z1 - z2z2) * h % P
    return (x3, y3, z3)


def g1_neg(p):
    return (p[0], -p[1] % P, p[2])


def g1_mul(p, k: int):
    out = G1_INF
    for bit in bin(k)[2:]:
        out = g1_dbl(out)
        if bit == "1":
            out = g1_add(out, p)
    return out


G1_GEN = (G1_X, G1_Y, 1)


def g1_affine_many(points) -> list:
    """Affine (x, y) of each point (None at infinity), with one
    inversion for all of them."""
    zs = [p[2] for p in points]
    invs = _batch_inv(zs, lambda a, b: a * b % P, lambda a: pow(a, P - 2, P), 1)
    out = []
    for (x, y, z), zi in zip(points, invs):
        if z == 0:
            out.append(None)
            continue
        zi2 = zi * zi % P
        out.append((x * zi2 % P, y * zi2 * zi % P))
    return out


def g1_compress(aff) -> bytes:
    if aff is None:
        return bytes([0xC0]) + bytes(47)
    b = bytearray(aff[0].to_bytes(48, "big"))
    b[0] |= 0x80 | (0x20 if aff[1] > HALF_P else 0)
    return bytes(b)


def g1_compress_many(points) -> list[bytes]:
    return [g1_compress(a) for a in g1_affine_many(points)]


def _batch_inv(values, mul, inv, one):
    """Montgomery's trick; zeros map to zero."""
    prefix = []
    acc = one
    for v in values:
        prefix.append(acc)
        if v != 0 and v != F2_ZERO:
            acc = mul(acc, v)
    acc = inv(acc)
    out = [None] * len(values)
    for i in range(len(values) - 1, -1, -1):
        v = values[i]
        if v == 0 or v == F2_ZERO:
            out[i] = v
            continue
        out[i] = mul(acc, prefix[i])
        acc = mul(acc, v)
    return out


# -- G2 (Jacobian over Fp2, y^2 = x^3 + 4(1 + u)) -----------------------------

G2_INF = (F2_ONE, F2_ONE, F2_ZERO)
B2 = (4, 4)


def g2_dbl(p):
    (x0, x1), (y0, y1), (z0, z1) = p
    if not (z0 or z1):
        return p
    a0, a1 = (x0 + x1) * (x0 - x1) % P, 2 * x0 * x1 % P  # x^2
    b0, b1 = (y0 + y1) * (y0 - y1) % P, 2 * y0 * y1 % P  # y^2
    c0, c1 = (b0 + b1) * (b0 - b1) % P, 2 * b0 * b1 % P  # y^4
    s0, s1 = x0 + b0, x1 + b1
    d0 = 2 * ((s0 + s1) * (s0 - s1) - a0 - c0) % P
    d1 = 2 * (2 * s0 * s1 - a1 - c1) % P
    e0, e1 = 3 * a0, 3 * a1
    x30 = ((e0 + e1) * (e0 - e1) - 2 * d0) % P
    x31 = (2 * e0 * e1 - 2 * d1) % P
    f0, f1 = d0 - x30, d1 - x31
    t0, t1 = e0 * f0, e1 * f1
    y30 = (t0 - t1 - 8 * c0) % P
    y31 = ((e0 + e1) * (f0 + f1) - t0 - t1 - 8 * c1) % P
    t0, t1 = y0 * z0, y1 * z1
    z30 = 2 * (t0 - t1) % P
    z31 = 2 * ((y0 + y1) * (z0 + z1) - t0 - t1) % P
    return ((x30, x31), (y30, y31), (z30, z31))


def g2_add(p, q):
    x1, y1, z1 = p
    x2, y2, z2 = q
    if z1 == F2_ZERO:
        return q
    if z2 == F2_ZERO:
        return p
    z1z1 = f2_sqr(z1)
    z2z2 = f2_sqr(z2)
    u1 = f2_mul(x1, z2z2)
    u2 = f2_mul(x2, z1z1)
    s1 = f2_mul(f2_mul(y1, z2), z2z2)
    s2 = f2_mul(f2_mul(y2, z1), z1z1)
    h = f2_sub(u2, u1)
    r = f2_scale(f2_sub(s2, s1), 2)
    if h == F2_ZERO:
        return g2_dbl(p) if r == F2_ZERO else G2_INF
    i = f2_scale(f2_sqr(h), 4)
    j = f2_mul(h, i)
    v = f2_mul(u1, i)
    x3 = f2_sub(f2_sub(f2_sqr(r), j), f2_scale(v, 2))
    y3 = f2_sub(f2_mul(r, f2_sub(v, x3)), f2_scale(f2_mul(s1, j), 2))
    z12 = f2_add(z1, z2)
    z3 = f2_mul(f2_sub(f2_sub(f2_sqr(z12), z1z1), z2z2), h)
    return (x3, y3, z3)


def g2_add_affine(p, q):
    """p Jacobian, q affine (x, y) and not at infinity."""
    (X0, X1), (Y0, Y1), (Z0, Z1) = p
    (qx0, qx1), (qy0, qy1) = q
    if not (Z0 or Z1):
        return ((qx0, qx1), (qy0, qy1), F2_ONE)
    zz0, zz1 = (Z0 + Z1) * (Z0 - Z1) % P, 2 * Z0 * Z1 % P  # z^2
    t0, t1 = qx0 * zz0, qx1 * zz1
    u0 = (t0 - t1) % P  # u2 = qx * z^2
    u1 = ((qx0 + qx1) * (zz0 + zz1) - t0 - t1) % P
    t0, t1 = Z0 * zz0, Z1 * zz1
    w0 = (t0 - t1) % P  # z^3
    w1 = ((Z0 + Z1) * (zz0 + zz1) - t0 - t1) % P
    t0, t1 = qy0 * w0, qy1 * w1
    s0 = (t0 - t1) % P  # s2 = qy * z^3
    s1 = ((qy0 + qy1) * (w0 + w1) - t0 - t1) % P
    h0, h1 = (u0 - X0) % P, (u1 - X1) % P
    r0, r1 = 2 * (s0 - Y0) % P, 2 * (s1 - Y1) % P
    if not (h0 or h1):
        return g2_dbl(p) if not (r0 or r1) else G2_INF
    hh0, hh1 = (h0 + h1) * (h0 - h1) % P, 2 * h0 * h1 % P
    i0, i1 = 4 * hh0, 4 * hh1
    t0, t1 = h0 * i0, h1 * i1
    j0 = (t0 - t1) % P
    j1 = ((h0 + h1) * (i0 + i1) - t0 - t1) % P
    t0, t1 = X0 * i0, X1 * i1
    v0 = (t0 - t1) % P
    v1 = ((X0 + X1) * (i0 + i1) - t0 - t1) % P
    x30 = ((r0 + r1) * (r0 - r1) - j0 - 2 * v0) % P
    x31 = (2 * r0 * r1 - j1 - 2 * v1) % P
    f0, f1 = v0 - x30, v1 - x31
    t0, t1 = r0 * f0, r1 * f1
    g0, g1 = Y0 * j0, Y1 * j1
    y30 = (t0 - t1 - 2 * (g0 - g1)) % P
    y31 = ((r0 + r1) * (f0 + f1) - t0 - t1
           - 2 * ((Y0 + Y1) * (j0 + j1) - g0 - g1)) % P
    a0, a1 = Z0 + h0, Z1 + h1
    z30 = ((a0 + a1) * (a0 - a1) - zz0 - hh0) % P
    z31 = (2 * a0 * a1 - zz1 - hh1) % P
    return ((x30, x31), (y30, y31), (z30, z31))


def g2_neg(p):
    return (p[0], f2_neg(p[1]), p[2])


def g2_mul_x_abs(p):
    """|x| * p (|x| has five bits set)."""
    out = G2_INF
    for bit in bin(X_ABS)[2:]:
        out = g2_dbl(out)
        if bit == "1":
            out = g2_add(out, p)
    return out


def g2_normalize_many(points):
    """Affine (x, y) for each Jacobian point (None at infinity), with one
    Fp2 inversion for all of them."""
    invs = _batch_inv([p[2] for p in points], f2_mul, f2_inv, F2_ONE)
    out = []
    for (x, y, z), zi in zip(points, invs):
        if z == F2_ZERO:
            out.append(None)
            continue
        zi2 = f2_sqr(zi)
        out.append((f2_mul(x, zi2), f2_mul(f2_mul(y, zi2), zi)))
    return out


def g2_mul_affine(q, k: int):
    """k * q for affine q, by a fixed 4-bit window of affine multiples."""
    table = [None, (q[0], q[1], F2_ONE)]
    for _ in range(14):
        table.append(g2_add_affine(table[-1], q))
    table = [None] + g2_normalize_many(table[1:])
    out = G2_INF
    bits = bin(k)[2:]
    bits = "0" * (-len(bits) % 4) + bits
    for i in range(0, len(bits), 4):
        if out[2] != F2_ZERO:
            for _ in range(4):
                out = g2_dbl(out)
        w = int(bits[i:i + 4], 2)
        if w:
            out = g2_add_affine(out, table[w])
    return out


def g2_compress(aff) -> bytes:
    if aff is None:
        return bytes([0xC0]) + bytes(95)
    (x0, x1), (y0, y1) = aff
    b = bytearray(x1.to_bytes(48, "big") + x0.to_bytes(48, "big"))
    larger = y1 > HALF_P if y1 else y0 > HALF_P
    b[0] |= 0x80 | (0x20 if larger else 0)
    return bytes(b)


# psi = untwist-Frobenius-twist: (x, y) -> (conj(x) * cx, conj(y) * cy)
_XI = (1, 1)
_PSI_CX = f2_inv(f2_pow(_XI, (P - 1) // 3))
_PSI_CY = f2_inv(f2_pow(_XI, (P - 1) // 2))


def g2_psi(p):
    x, y, z = p
    return (f2_mul(f2_conj(x), _PSI_CX), f2_mul(f2_conj(y), _PSI_CY),
            f2_conj(z))


def clear_cofactor(p):
    """h_eff * p, RFC 9380 appendix G.3 (clear_cofactor_bls12381_g2)."""
    t1 = g2_neg(g2_mul_x_abs(p))  # x * p, x < 0
    t2 = g2_psi(p)
    t3 = g2_psi(g2_psi(g2_dbl(p)))
    t3 = g2_add(t3, g2_neg(t2))
    t2 = g2_add(t1, t2)
    t2 = g2_neg(g2_mul_x_abs(t2))
    t3 = g2_add(t3, t2)
    t3 = g2_add(t3, g2_neg(t1))
    return g2_add(t3, g2_neg(p))


# -- hash_to_curve (RFC 9380, BLS12381G2_XMD:SHA-256_SSWU_RO_) ----------------

_A_ISO = (0, 240)  # E2': y^2 = x^3 + A'x + B'
_B_ISO = (1012, 1012)
_Z_SSWU = (P - 2, P - 1)  # -(2 + u)


def expand_message_xmd(msg: bytes, dst: bytes, n: int) -> bytes:
    ell = (n + 31) // 32
    dst_prime = dst + bytes([len(dst)])
    b0 = hashlib.sha256(
        bytes(64) + msg + n.to_bytes(2, "big") + b"\x00" + dst_prime
    ).digest()
    out = [hashlib.sha256(b0 + b"\x01" + dst_prime).digest()]
    for i in range(2, ell + 1):
        mixed = bytes(a ^ b for a, b in zip(b0, out[-1]))
        out.append(hashlib.sha256(mixed + bytes([i]) + dst_prime).digest())
    return b"".join(out)[:n]


def hash_to_field(msg: bytes, dst: bytes = DST_G2):
    """Two Fp2 elements (count = 2, L = 64)."""
    data = expand_message_xmd(msg, dst, 256)
    e = [int.from_bytes(data[64 * i:64 * (i + 1)], "big") % P
         for i in range(4)]
    return (e[0], e[1]), (e[2], e[3])


def _sswu(u):
    """Simplified SWU onto E2' (RFC 9380 section 6.6.2), affine."""
    a, b, z = _A_ISO, _B_ISO, _Z_SSWU
    zu2 = f2_mul(z, f2_sqr(u))
    tv = f2_add(f2_sqr(zu2), zu2)
    if tv == F2_ZERO:
        x1 = f2_mul(b, f2_inv(f2_mul(z, a)))
    else:
        x1 = f2_mul(f2_mul(f2_neg(b), f2_inv(a)), f2_add(F2_ONE, f2_inv(tv)))
    gx1 = f2_add(f2_mul(f2_add(f2_sqr(x1), a), x1), b)
    y = f2_sqrt(gx1)
    x = x1
    if y is None:
        x = f2_mul(zu2, x1)
        y = f2_sqrt(f2_add(f2_mul(f2_add(f2_sqr(x), a), x), b))
    if f2_sgn0(y) != f2_sgn0(u):
        y = f2_neg(y)
    return x, y


def _velu_params():
    """The 3-isogeny E2' -> E2 by Velu's formulas. Its kernel point x0
    makes the codomain's linear coefficient A' - 5t vanish, with
    t = 6*x0^2 + 2A': x0^2 = -3A'/10. The codomain is then
    y^2 = x^3 + lam^6 * 4(1 + u) with lam = 3; composing with
    (x, y) -> (x / lam^2, y / lam^3) for lam = -3 gives the map of
    RFC 9380 appendix E.3. Of the two roots x0 we take the one whose
    (c1, c0) is smaller that is a root of the 3-division polynomial
    3x^4 + 6A'x^2 + 12B'x - A'^2."""
    a, b = _A_ISO, _B_ISO
    r = f2_sqrt(f2_mul(f2_scale(a, -3 % P), f2_inv((10, 0))))
    found = []
    for x0 in (r, f2_neg(r)):
        x2 = f2_sqr(x0)
        psi3 = f2_sub(
            f2_add(f2_add(f2_scale(f2_sqr(x2), 3), f2_scale(f2_mul(a, x2), 6)),
                   f2_scale(f2_mul(b, x0), 12)),
            f2_sqr(a))
        if psi3 != F2_ZERO:
            continue
        t = f2_add(f2_scale(x2, 6), f2_scale(a, 2))
        u = f2_scale(f2_add(f2_mul(f2_add(x2, a), x0), b), 4)
        b_new = f2_sub(b, f2_scale(f2_add(u, f2_mul(t, x0)), 7))
        if f2_sub(f2_scale(a, 1), f2_scale(t, 5)) != F2_ZERO:
            continue
        if b_new != f2_scale(B2, 729):
            continue
        found.append((x0, t, u))
    if not found:
        raise ArithmeticError("no 3-isogeny E2' -> E2")
    found.sort(key=lambda c: (c[0][1], c[0][0]))
    return found[0]


_ISO_X0, _ISO_T, _ISO_U = _velu_params()
_INV_L2 = pow(9, P - 2, P)  # 1 / lam^2, lam = -3
_INV_L3 = pow(-27 % P, P - 2, P)  # 1 / lam^3


def _iso_map(x, y):
    d_inv = f2_inv(f2_sub(x, _ISO_X0))
    d_inv2 = f2_sqr(d_inv)
    d_inv3 = f2_mul(d_inv2, d_inv)
    xx = f2_add(f2_add(x, f2_mul(_ISO_T, d_inv)), f2_mul(_ISO_U, d_inv2))
    dx = f2_sub(f2_sub(F2_ONE, f2_mul(_ISO_T, d_inv2)),
                f2_scale(f2_mul(_ISO_U, d_inv3), 2))
    return f2_scale(xx, _INV_L2), f2_scale(f2_mul(y, dx), _INV_L3)


def hash_to_g2(msg: bytes, dst: bytes = DST_G2):
    """H(msg) in G2, affine."""
    u0, u1 = hash_to_field(msg, dst)
    q0 = _iso_map(*_sswu(u0))
    q1 = _iso_map(*_sswu(u1))
    s = g2_add_affine((q0[0], q0[1], F2_ONE), q1)
    return g2_normalize_many([clear_cofactor(s)])[0]


# -- the plain reference ------------------------------------------------------


def pubkey(sk: int) -> bytes:
    return g1_compress_many([g1_mul(G1_GEN, sk % R)])[0]


def sign(sk: int, msg: bytes, dst: bytes = DST_G2) -> bytes:
    h = hash_to_g2(msg, dst)
    return g2_compress(g2_normalize_many([g2_mul_affine(h, sk % R)])[0])


def reference_verdict(sk: int, pk: bytes, msg: bytes, sig: bytes,
                      dst: bytes = DST_G2) -> bool:
    """Whether (pk, msg, sig) is a valid set for the key sk, computed
    directly: pk = sk * g1 and sig = sk * H(msg). For pk in G1 and sig in
    G2 this is the pairing equation e(pk, H(msg)) = e(g1, sig), since the
    pairing is non-degenerate and both groups have prime order r; the
    traffic knows every sk, so the direct form needs no pairing."""
    return pubkey(sk) == pk and sign(sk, msg, dst) == sig

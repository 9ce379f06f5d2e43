"""BLS12-381 arithmetic for the benchmark's traffic and its reference check.

Written from the public definitions (the curve constants, RFC 9380's
BLS12381G2_XMD:SHA-256_SSWU_RO_ suite with its Appendix E.3 isogeny table,
and the ZCash point encoding) and importing nothing of the program under
test, so a fault in the program's curve code cannot hide in the check.
Plain Python integers; affine points are (x, y) tuples, Jacobian points
(X, Y, Z); an Fq2 element is a pair (c0, c1) meaning c0 + c1*u, u^2 = -1.
"""

from __future__ import annotations

import hashlib

P = 0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFAAAB
R = 0x73EDA753299D7D483339D80809A1D80553BDA402FFFE5BFEFFFFFFFF00000001
G1 = (
    0x17F1D3A73197D7942695638C4FA9AC0FC3688C4F9774B905A14E3A3F171BAC586C55E83FF97A1AEFFB3AF00ADB22C6BB,
    0x08B3F481E3AAA0F1A09E30ED741D8AE4FCF5E095D5D00AF600DB18CB2C04B3EDD03CC744A2888AE40CAA232946C5E7E1,
)
#: RFC 9380 section 8.8.2 effective cofactor of G2
H_EFF = 0xBC69F08F2EE75B3584C6A0EA91B352888E2A8E9145AD7689986FF031508FFE1329C2F178731DB956D82BF015D1212B02EC0EC69D7477C1AE954CBC06689F6A359894C0ADEBBF6B4E8020005AAA95551
DST_POP = b"BLS_SIG_BLS12381G2_XMD:SHA-256_SSWU_RO_POP_"
_HALF = (P - 1) // 2

# --- Fq2 -------------------------------------------------------------------


def f2_add(a, b):
    return ((a[0] + b[0]) % P, (a[1] + b[1]) % P)


def f2_sub(a, b):
    return ((a[0] - b[0]) % P, (a[1] - b[1]) % P)


def f2_neg(a):
    return (-a[0] % P, -a[1] % P)


def f2_mul(a, b):
    t0 = a[0] * b[0]
    t1 = a[1] * b[1]
    return ((t0 - t1) % P, ((a[0] + a[1]) * (b[0] + b[1]) - t0 - t1) % P)


def f2_sqr(a):
    return ((a[0] + a[1]) * (a[0] - a[1]) % P, 2 * a[0] * a[1] % P)


def f2_inv(a):
    inv = pow(a[0] * a[0] + a[1] * a[1], -1, P)
    return (a[0] * inv % P, -a[1] * inv % P)


def _is_square_fq(v: int) -> bool:
    return v == 0 or pow(v, _HALF, P) == 1


def _sqrt_fq(v: int) -> int | None:
    s = pow(v, (P + 1) // 4, P)
    return s if s * s % P == v % P else None


def f2_sqrt(a):
    """A square root of a in Fq2, or None (the complex method: Fq2 has
    u^2 = -1 and p = 3 mod 4)."""
    a0, a1 = a
    if a1 == 0:
        s = _sqrt_fq(a0)
        return (s, 0) if s is not None else (0, _sqrt_fq(-a0 % P))
    alpha = _sqrt_fq((a0 * a0 + a1 * a1) % P)
    if alpha is None:
        return None
    inv2 = (P + 1) // 2
    delta = (a0 + alpha) * inv2 % P
    if not _is_square_fq(delta):
        delta = (a0 - alpha) * inv2 % P
    x0 = _sqrt_fq(delta)
    x1 = a1 * pow(2 * x0, -1, P) % P
    out = (x0, x1)
    return out if f2_sqr(out) == (a0 % P, a1 % P) else None


def f2_sgn0(a) -> int:
    return (a[0] & 1) | ((a[0] == 0) & (a[1] & 1))


# --- G1 (y^2 = x^3 + 4 over Fq) ----------------------------------------------


def g1_affine_chain(start, step, count: int) -> list:
    """[start + i*step for i in range(count)] for affine start and step,
    as affine points, with one field inversion per block of additions
    (Montgomery's batch trick). Assumes no sum passes the point at
    infinity, which holds for the registry's consecutive secret keys."""
    out = [start]
    block = 4096
    step_mults = [step]
    while len(step_mults) < block:  # step, 2 step, ..., block*step
        step_mults.append(_g1_add_affine(step_mults[-1], step))
    while len(out) < count:
        base = out[-1]
        n = min(block, count - len(out))
        dens = [(m[0] - base[0]) % P for m in step_mults[:n]]
        invs = _batch_inv(dens)
        bx, by = base
        for (mx, my), inv in zip(step_mults[:n], invs):
            lam = (my - by) * inv % P
            x3 = (lam * lam - bx - mx) % P
            out.append((x3, (lam * (bx - x3) - by) % P))
    return out


def _g1_add_affine(a, b):
    if a[0] == b[0]:
        lam = 3 * a[0] * a[0] * pow(2 * a[1], -1, P) % P
    else:
        lam = (b[1] - a[1]) * pow(b[0] - a[0], -1, P) % P
    x3 = (lam * lam - a[0] - b[0]) % P
    return (x3, (lam * (a[0] - x3) - a[1]) % P)


def g1_mul_affine(pt, k: int):
    """k * pt for an affine point, as an affine point (k > 0)."""
    acc = None
    for bit in bin(k)[2:]:
        if acc is not None:
            acc = _g1_add_affine(acc, acc)
        if bit == "1":
            acc = pt if acc is None else _g1_add_affine(acc, pt)
    return acc


def _batch_inv(vals: list) -> list:
    prefix = []
    acc = 1
    for v in vals:
        prefix.append(acc)
        acc = acc * v % P
    inv = pow(acc, -1, P)
    out = [0] * len(vals)
    for i in range(len(vals) - 1, -1, -1):
        out[i] = prefix[i] * inv % P
        inv = inv * vals[i] % P
    return out


def g1_compress(pt) -> bytes:
    x, y = pt
    b = bytearray(x.to_bytes(48, "big"))
    b[0] |= 0x80 | (0x20 if y > _HALF else 0)
    return bytes(b)


# --- G2 (y^2 = x^3 + 4(1 + u) over Fq2), Jacobian ------------------------------

G2_INF = ((1, 0), (1, 0), (0, 0))


def g2_double(pt):
    X, Y, Z = pt
    if Z == (0, 0):
        return pt
    A = f2_sqr(X)
    B = f2_sqr(Y)
    C = f2_sqr(B)
    D = f2_sub(f2_sqr(f2_add(X, B)), f2_add(A, C))
    D = f2_add(D, D)
    E = f2_add(f2_add(A, A), A)
    F = f2_sqr(E)
    X3 = f2_sub(F, f2_add(D, D))
    C8 = f2_add(C, C)
    C8 = f2_add(C8, C8)
    C8 = f2_add(C8, C8)
    Y3 = f2_sub(f2_mul(E, f2_sub(D, X3)), C8)
    YZ = f2_mul(Y, Z)
    return (X3, Y3, f2_add(YZ, YZ))


def g2_add(p1, p2):
    if p1[2] == (0, 0):
        return p2
    if p2[2] == (0, 0):
        return p1
    X1, Y1, Z1 = p1
    X2, Y2, Z2 = p2
    Z1Z1 = f2_sqr(Z1)
    Z2Z2 = f2_sqr(Z2)
    U1 = f2_mul(X1, Z2Z2)
    U2 = f2_mul(X2, Z1Z1)
    S1 = f2_mul(f2_mul(Y1, Z2), Z2Z2)
    S2 = f2_mul(f2_mul(Y2, Z1), Z1Z1)
    H = f2_sub(U2, U1)
    r = f2_sub(S2, S1)
    if H == (0, 0):
        return g2_double(p1) if r == (0, 0) else G2_INF
    HH = f2_sqr(H)
    HHH = f2_mul(H, HH)
    V = f2_mul(U1, HH)
    X3 = f2_sub(f2_sub(f2_sqr(r), HHH), f2_add(V, V))
    Y3 = f2_sub(f2_mul(r, f2_sub(V, X3)), f2_mul(S1, HHH))
    return (X3, Y3, f2_mul(f2_mul(Z1, Z2), H))


def g2_add_affine(p1, q):
    """Jacobian p1 + affine q (mixed addition)."""
    if p1[2] == (0, 0):
        return (q[0], q[1], (1, 0))
    X1, Y1, Z1 = p1
    Z1Z1 = f2_sqr(Z1)
    U2 = f2_mul(q[0], Z1Z1)
    S2 = f2_mul(f2_mul(q[1], Z1), Z1Z1)
    H = f2_sub(U2, X1)
    r = f2_sub(S2, Y1)
    if H == (0, 0):
        return g2_double(p1) if r == (0, 0) else G2_INF
    HH = f2_sqr(H)
    HHH = f2_mul(H, HH)
    V = f2_mul(X1, HH)
    X3 = f2_sub(f2_sub(f2_sqr(r), HHH), f2_add(V, V))
    Y3 = f2_sub(f2_mul(r, f2_sub(V, X3)), f2_mul(Y1, HHH))
    return (X3, Y3, f2_mul(Z1, H))


def g2_mul(pt, k: int):
    """k * pt (Jacobian in, Jacobian out), plain double-and-add."""
    acc = G2_INF
    for bit in bin(k)[2:]:
        acc = g2_double(acc)
        if bit == "1":
            acc = g2_add(acc, pt)
    return acc


def g2_to_affine_many(pts: list) -> list:
    """Jacobian → affine for many points with one Fq2 inversion."""
    zs = [p[2] for p in pts]
    prefix = []
    acc = (1, 0)
    for z in zs:
        prefix.append(acc)
        acc = f2_mul(acc, z)
    inv = f2_inv(acc)
    out = [None] * len(pts)
    for i in range(len(pts) - 1, -1, -1):
        zi = f2_mul(prefix[i], inv)
        inv = f2_mul(inv, zs[i])
        zi2 = f2_sqr(zi)
        X, Y, _ = pts[i]
        out[i] = (f2_mul(X, zi2), f2_mul(Y, f2_mul(zi2, zi)))
    return out


def g2_compress(aff) -> bytes:
    (x0, x1), (y0, y1) = aff
    big = y1 > _HALF if y1 else y0 > _HALF
    b = bytearray(x1.to_bytes(48, "big") + x0.to_bytes(48, "big"))
    b[0] |= 0x80 | (0x20 if big else 0)
    return bytes(b)


# --- hash to G2 (RFC 9380) ------------------------------------------------------


def expand_message_xmd(msg: bytes, dst: bytes, n: int) -> bytes:
    ell = -(-n // 32)
    dst_prime = dst + bytes([len(dst)])
    b0 = hashlib.sha256(bytes(64) + msg + n.to_bytes(2, "big") + b"\x00" + dst_prime).digest()
    out = [hashlib.sha256(b0 + b"\x01" + dst_prime).digest()]
    for i in range(2, ell + 1):
        mixed = bytes(x ^ y for x, y in zip(b0, out[-1]))
        out.append(hashlib.sha256(mixed + bytes([i]) + dst_prime).digest())
    return b"".join(out)[:n]


_A = (0, 240)
_B = (1012, 1012)
_Z = (P - 2, P - 1)
# RFC 9380 Appendix E.3, the 3-isogeny E2' -> E2: k_(i,j) by ascending power
_K1 = [
    (0x5C759507E8E333EBB5B7A9A47D7ED8532C52D39FD3A042A88B58423C50AE15D5C2638E343D9C71C6238AAAAAAAA97D6,
     0x5C759507E8E333EBB5B7A9A47D7ED8532C52D39FD3A042A88B58423C50AE15D5C2638E343D9C71C6238AAAAAAAA97D6),
    (0, 0x11560BF17BAA99BC32126FCED787C88F984F87ADF7AE0C7F9A208C6B4F20A4181472AAA9CB8D555526A9FFFFFFFFC71A),
    (0x11560BF17BAA99BC32126FCED787C88F984F87ADF7AE0C7F9A208C6B4F20A4181472AAA9CB8D555526A9FFFFFFFFC71E,
     0x8AB05F8BDD54CDE190937E76BC3E447CC27C3D6FBD7063FCD104635A790520C0A395554E5C6AAAA9354FFFFFFFFE38D),
    (0x171D6541FA38CCFAED6DEA691F5FB614CB14B4E7F4E810AA22D6108F142B85757098E38D0F671C7188E2AAAAAAAA5ED1, 0),
]
_K2 = [(0, P - 72), (12, P - 12), (1, 0)]
_K3 = [
    (0x1530477C7AB4113B59A4C18B076D11930F7DA5D4A07F649BF54439D87D27E500FC8C25EBF8C92F6812CFC71C71C6D706,
     0x1530477C7AB4113B59A4C18B076D11930F7DA5D4A07F649BF54439D87D27E500FC8C25EBF8C92F6812CFC71C71C6D706),
    (0, 0x5C759507E8E333EBB5B7A9A47D7ED8532C52D39FD3A042A88B58423C50AE15D5C2638E343D9C71C6238AAAAAAAA97BE),
    (0x11560BF17BAA99BC32126FCED787C88F984F87ADF7AE0C7F9A208C6B4F20A4181472AAA9CB8D555526A9FFFFFFFFC71C,
     0x8AB05F8BDD54CDE190937E76BC3E447CC27C3D6FBD7063FCD104635A790520C0A395554E5C6AAAA9354FFFFFFFFE38F),
    (0x124C9AD43B6CF79BFBF7043DE3811AD0761B0F37A1E26286B0E977C69AA274524E79097A56DC4BD9E1B371C71C718B10, 0),
]
_K4 = [(P - 432, P - 432), (0, P - 216), (18, P - 18), (1, 0)]


def _poly(coeffs, x):
    acc = (0, 0)
    for c in reversed(coeffs):
        acc = f2_add(f2_mul(acc, x), c)
    return acc


def _sswu(u):
    zu2 = f2_mul(_Z, f2_sqr(u))
    tv = f2_add(f2_sqr(zu2), zu2)
    if tv == (0, 0):
        x1 = f2_mul(_B, f2_inv(f2_mul(_Z, _A)))
    else:
        x1 = f2_mul(f2_mul(f2_neg(_B), f2_inv(_A)), f2_add((1, 0), f2_inv(tv)))
    gx1 = f2_add(f2_add(f2_mul(f2_sqr(x1), x1), f2_mul(_A, x1)), _B)
    y = f2_sqrt(gx1)
    x = x1
    if y is None:
        x = f2_mul(zu2, x1)
        y = f2_sqrt(f2_add(f2_add(f2_mul(f2_sqr(x), x), f2_mul(_A, x)), _B))
    if f2_sgn0(u) != f2_sgn0(y):
        y = f2_neg(y)
    return x, y


def _iso3(pt):
    x, y = pt
    xn, xd = _poly(_K1, x), _poly(_K2, x)
    yn, yd = _poly(_K3, x), _poly(_K4, x)
    return f2_mul(xn, f2_inv(xd)), f2_mul(y, f2_mul(yn, f2_inv(yd)))


def hash_to_g2(msg: bytes, dst: bytes = DST_POP):
    """RFC 9380 hash_to_curve, BLS12381G2_XMD:SHA-256_SSWU_RO_; Jacobian."""
    uniform = expand_message_xmd(msg, dst, 256)
    us = [
        (int.from_bytes(uniform[i : i + 64], "big") % P,
         int.from_bytes(uniform[i + 64 : i + 128], "big") % P)
        for i in (0, 128)
    ]
    q0, q1 = (_iso3(_sswu(u)) for u in us)
    s = g2_add_affine((q0[0], q0[1], (1, 0)), q1)
    return g2_mul(s, H_EFF)

"""The benchmark's own curve arithmetic against RFC 9380's known answers
and against the program's host backend (a second witness)."""

from benchmark import bls

QUUX = b"QUUX-V01-CS02-with-BLS12381G2_XMD:SHA-256_SSWU_RO_"
# RFC 9380 J.10.1, BLS12381G2_XMD:SHA-256_SSWU_RO_
VECTORS = [
    (b"", (
        0x0141EBFBDCA40EB85B87142E130AB689C673CF60F1A3E98D69335266F30D9B8D4AC44C1038E9DCDD5393FAF5C41FB78A,
        0x05CB8437535E20ECFFAEF7752BADDF98034139C38452458BAEEFAB379BA13DFF5BF5DD71B72418717047F5B0F37DA03D,
        0x0503921D7F6A12805E72940B963C0CF3471C7B2A524950CA195D11062EE75EC076DAF2D4BC358C4B190C0C98064FDD92,
        0x12424AC32561493F3FE3C260708A12B7C620E7BE00099A974E259DDC7D1F6395C3C811CDD19F1E8DBF3E9ECFDCBAB8D6,
    )),
    (b"abc", (
        0x02C2D18E033B960562AAE3CAB37A27CE00D80CCD5BA4B7FE0E7A210245129DBEC7780CCC7954725F4168AFF2787776E6,
        0x139CDDBCCDC5E91B9623EFD38C49F81A6F83F175E80B06FC374DE9EB4B41DFE4CA3A230ED250FBE3A2ACF73A41177FD8,
        0x1787327B68159716A37440985269CF584BCB1E621D3A7202BE6EA05C4CFE244AEB197642555A0645FB87BF7466B2BA48,
        0x00AA65DAE3C8D732D10ECD2C50F8A1BAF3001578F71C694E03866E9F3D49AC1E1CE70DD94A733534F106D4CEC0EDDD16,
    )),
]


def test_hash_to_g2_rfc_vectors():
    for msg, (x0, x1, y0, y1) in VECTORS:
        (x, y), = bls.g2_to_affine_many([bls.hash_to_g2(msg, QUUX)])
        assert (x, y) == ((x0, x1), (y0, y1))


def test_signatures_and_keys_match_the_program():
    from lighthouse_tpu.crypto import bls as node_bls

    for sk, msg in ((1, b"\x00" * 32), (0x9E3779B97F4A7C15 + 77, b"\x42" * 32)):
        h = bls.hash_to_g2(msg)
        sig = bls.g2_compress(bls.g2_to_affine_many([bls.g2_mul(h, sk)])[0])
        assert sig == node_bls.SecretKey(sk).sign(msg).to_bytes()
        pk = bls.g1_compress(bls.g1_mul_affine(bls.G1, sk))
        assert pk == node_bls.SecretKey(sk).public_key().to_bytes()


def test_key_chain_is_consecutive_secret_keys_and_distinct():
    s0 = 0x9E3779B97F4A7C15
    start = bls.g1_mul_affine(bls.G1, s0)
    pts = bls.g1_affine_chain(start, bls.G1, 4096 + 300)
    for v in (0, 1, 4095, 4096, 4097, 4395):
        assert pts[v] == bls.g1_mul_affine(bls.G1, s0 + v)
    assert len({p[0] for p in pts}) == len(pts)

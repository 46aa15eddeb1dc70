"""Native quantity parser: build, parity with the pure-Python oracle, and
fallback behavior.

The C parser (karpenter_tpu/native/quantity.c) must agree EXACTLY —
value as a Fraction and display format — with the regex+Fraction oracle in
utils/quantity.py for every string either accepts, and must reject (raise,
triggering fallback) anything outside its exact-arithmetic range rather
than silently losing precision.
"""

from fractions import Fraction

import pytest

from karpenter_tpu.native import load_kquantity
from karpenter_tpu.utils.quantity import (
    _NATIVE_FORMATS,
    _QUANTITY_RE,
    Quantity,
)

native = load_kquantity()

pytestmark = pytest.mark.skipif(
    native is None, reason="no C toolchain available"
)


def _regex_parse(s):
    """The pure-Python oracle, bypassing the native fast path."""
    from karpenter_tpu.utils.quantity import (
        _BINARY_SUFFIXES,
        _DECIMAL_SUFFIXES,
        BINARY_SI,
        DECIMAL_EXPONENT,
        DECIMAL_SI,
    )

    m = _QUANTITY_RE.match(s.strip())
    num = Fraction(m.group("num"))
    if m.group("sign") == "-":
        num = -num
    suffix, exp = m.group("suffix"), m.group("exp")
    if suffix in _BINARY_SUFFIXES:
        return num * _BINARY_SUFFIXES[suffix], BINARY_SI
    if suffix is not None:
        return num * _DECIMAL_SUFFIXES[suffix], DECIMAL_SI
    if exp is not None:
        return num * Fraction(10) ** int(exp[1:]), DECIMAL_EXPONENT
    return num, DECIMAL_SI


CASES = [
    "0", "1", "100m", "1500m", "1100m", "25Gi", "99", "128500Mi", "1.5",
    "0.5", ".5", "5.", "1Ki", "2Mi", "3Ti", "4Pi", "1Ei", "1n", "2u",
    "3k", "4M", "5G", "6T", "7P", "1E", "-1", "-100m", "+2Gi", "1e3",
    "1E3", "2e-3", "1.25e2", "  25Gi  ", "0.000001", "123.456789",
    "110", "7600m", "48900m", "77Gi", "385500Mi", "150",
]


class TestParity:
    @pytest.mark.parametrize("s", CASES)
    def test_exact_value_and_format(self, s):
        num, den, fmt = native.parse(s)
        value, expect_format = _regex_parse(s)
        assert Fraction(num, den) == value, s
        assert _NATIVE_FORMATS[fmt] == expect_format, s

    def test_fuzz_against_oracle(self):
        import random

        rng = random.Random(11)
        suffixes = ["", "m", "k", "M", "G", "Ki", "Mi", "Gi", "Ti", "n",
                    "u", "T", "P", "E", "Pi", "Ei", "e2", "e-4", "E+6"]
        for _ in range(3000):
            mantissa = rng.choice(
                [
                    str(rng.randint(0, 10**rng.randint(1, 12))),
                    f"{rng.randint(0, 10**6)}.{rng.randint(0, 10**6)}",
                    f".{rng.randint(1, 10**6)}",
                ]
            )
            sign = rng.choice(["", "-", "+"])
            s = sign + mantissa + rng.choice(suffixes)
            try:
                num, den, fmt = native.parse(s)
            except ValueError:
                continue  # native declined; fallback handles it
            value, expect_format = _regex_parse(s)
            assert Fraction(num, den) == value, s
            assert _NATIVE_FORMATS[fmt] == expect_format, s

    @pytest.mark.parametrize(
        "s", ["", "abc", "1.2.3", "1X", "Ki", "--1", "1e", "1ee3", ".",
              "1 2", "0x10", "1\x00", "2.5\x00", "\x00", "1Gi\x00"]
    )
    def test_rejects_invalid(self, s):
        with pytest.raises(ValueError):
            native.parse(s)
        assert _QUANTITY_RE.match(s.strip()) is None

    def test_overflow_declines_instead_of_truncating(self):
        with pytest.raises(ValueError):
            native.parse("9" * 60)  # > u128
        # but the public API still parses it via the Python path
        assert Quantity.parse("9" * 60).value == Fraction("9" * 60)


class TestAsyncLoad:
    def test_background_build_becomes_visible(self):
        """The public parse path must converge to the native parser without
        ever blocking on the compile."""
        import time

        from karpenter_tpu import native as native_pkg
        from karpenter_tpu.utils.quantity import _native_parser

        _native_parser()  # kicks the async load (or it already ran)
        deadline = time.time() + 30
        while native_pkg.peek_kquantity() is None and time.time() < deadline:
            time.sleep(0.05)
        assert native_pkg.peek_kquantity() is not None
        assert _native_parser() is native_pkg.peek_kquantity()


class TestIntegration:
    def test_public_parse_uses_same_semantics(self):
        # whole pipeline: canonical formatting must be unchanged
        assert str(Quantity.parse("25Gi")) == "25Gi"
        assert str(Quantity.parse("1100m")) == "1100m"
        assert str(Quantity.parse("128500Mi")) == "128500Mi"
        assert Quantity.parse("1500m").to_float() == pytest.approx(1.5)
        total = Quantity()
        for _ in range(77):
            total = total.add(Quantity.parse("1Gi"))
        assert str(total) == "77Gi"

    def test_speedup_sanity(self):
        """The native path should beat the regex+Fraction oracle; parity
        matters more than the ratio, so just assert it is not slower."""
        import time

        strings = CASES * 200
        native_parse = native.parse
        t0 = time.perf_counter()
        for s in strings:
            try:
                native_parse(s)
            except ValueError:
                pass
        t_native = time.perf_counter() - t0
        t0 = time.perf_counter()
        for s in strings:
            _regex_parse(s)
        t_python = time.perf_counter() - t0
        assert t_native < t_python


class TestSourceKeyedBuild:
    def test_object_name_follows_the_source(self, tmp_path, monkeypatch):
        """The built object is looked up by a hash of its .c source, so a
        copied or stale .so built from other source is never loaded,
        whatever its mtime."""
        from karpenter_tpu import native as N

        monkeypatch.setattr(N, "_HERE", str(tmp_path))
        monkeypatch.setattr(N, "_BUILD_DIR", str(tmp_path / "_build"))
        src = tmp_path / "k.c"
        src.write_text("int f(void) { return 1; }\n")
        first = N.built_path("k.c", "_k")
        assert first == N.built_path("k.c", "_k")
        src.write_text("int f(void) { return 2; }\n")
        second = N.built_path("k.c", "_k")
        assert second != first
        assert second.startswith(str(tmp_path / "_build" / "_k-"))

"""JSON lexer/parser — the ballet/json counterpart.

Counterpart of /root/reference/src/ballet/json/ (cJSON-derived lexer
feeding the RPC server).  A recursive-descent parser with the strictness
an RPC boundary needs: depth-limited (stack safety against adversarial
nesting), duplicate-key detection optional, strict number grammar, and
\\uXXXX escapes incl. surrogate pairs.  `loads` returns plain Python
values; `dumps` is the matching compact encoder (sorted keys optional).

The point of owning this instead of the stdlib: the RPC and metrics
servers sit on untrusted sockets, and the parser's failure modes
(depth, size, grammar) must be explicit and tested — the same reason
the reference vendors its own lexer.
"""

from __future__ import annotations

MAX_DEPTH = 64
MAX_LEN = 16 * 1024 * 1024
MAX_NUMBER_DIGITS = 400  # int(text) past ~4300 digits raises ValueError
# JSON's digits are ASCII: str.isdigit() also takes the likes of "²",
# which int() then refuses with a ValueError no caller expects
_DIGITS = "0123456789"
# on CPython >= 3.11; the contract here is JsonError for any bad input

_WS = " \t\n\r"
_ESC = {'"': '"', "\\": "\\", "/": "/", "b": "\b", "f": "\f",
        "n": "\n", "r": "\r", "t": "\t"}
_REV_ESC = {v: "\\" + k for k, v in _ESC.items() if k != "/"}


class JsonError(ValueError):
    def __init__(self, msg: str, pos: int):
        super().__init__(f"{msg} at offset {pos}")
        self.pos = pos


class _Parser:
    def __init__(self, s: str, *, reject_duplicate_keys: bool):
        self.s = s
        self.i = 0
        self.n = len(s)
        self.reject_dups = reject_duplicate_keys

    def err(self, msg):
        raise JsonError(msg, self.i)

    def skip_ws(self):
        while self.i < self.n and self.s[self.i] in _WS:
            self.i += 1

    def expect(self, ch):
        if self.i >= self.n or self.s[self.i] != ch:
            self.err(f"expected {ch!r}")
        self.i += 1

    def value(self, depth):
        if depth > MAX_DEPTH:
            self.err("nesting too deep")
        self.skip_ws()
        if self.i >= self.n:
            self.err("unexpected end of input")
        c = self.s[self.i]
        if c == "{":
            return self.obj(depth)
        if c == "[":
            return self.arr(depth)
        if c == '"':
            return self.string()
        if c == "t":
            return self.lit("true", True)
        if c == "f":
            return self.lit("false", False)
        if c == "n":
            return self.lit("null", None)
        if c == "-" or c.isdigit():
            return self.number()
        self.err(f"unexpected character {c!r}")

    def lit(self, word, val):
        if self.s[self.i : self.i + len(word)] != word:
            self.err(f"bad literal")
        self.i += len(word)
        return val

    def obj(self, depth):
        self.expect("{")
        out = {}
        self.skip_ws()
        if self.i < self.n and self.s[self.i] == "}":
            self.i += 1
            return out
        while True:
            self.skip_ws()
            key = self.string()
            if self.reject_dups and key in out:
                self.err(f"duplicate key {key!r}")
            self.skip_ws()
            self.expect(":")
            out[key] = self.value(depth + 1)
            self.skip_ws()
            if self.i >= self.n:
                self.err("unterminated object")
            if self.s[self.i] == ",":
                self.i += 1
                continue
            if self.s[self.i] == "}":
                self.i += 1
                return out
            self.err("expected ',' or '}'")

    def arr(self, depth):
        self.expect("[")
        out = []
        self.skip_ws()
        if self.i < self.n and self.s[self.i] == "]":
            self.i += 1
            return out
        while True:
            out.append(self.value(depth + 1))
            self.skip_ws()
            if self.i >= self.n:
                self.err("unterminated array")
            if self.s[self.i] == ",":
                self.i += 1
                continue
            if self.s[self.i] == "]":
                self.i += 1
                return out
            self.err("expected ',' or ']'")

    def string(self):
        self.expect('"')
        out = []
        while True:
            if self.i >= self.n:
                self.err("unterminated string")
            c = self.s[self.i]
            if c == '"':
                self.i += 1
                return "".join(out)
            if c == "\\":
                self.i += 1
                if self.i >= self.n:
                    self.err("bad escape")
                e = self.s[self.i]
                if e in _ESC:
                    out.append(_ESC[e])
                    self.i += 1
                elif e == "u":
                    out.append(self._unicode_escape())
                else:
                    self.err(f"bad escape \\{e}")
            elif ord(c) < 0x20:
                self.err("control character in string")
            else:
                out.append(c)
                self.i += 1

    def _unicode_escape(self):
        def hex4():
            h = self.s[self.i + 1 : self.i + 5]
            # explicit hex-digit check: int(h, 16) accepts '+', '_',
            # whitespace — all invalid JSON
            if len(h) != 4 or any(c not in "0123456789abcdefABCDEF"
                                  for c in h):
                self.err("bad \\u escape")
            v = int(h, 16)
            self.i += 5
            return v

        v = hex4()
        if 0xD800 <= v <= 0xDBFF:  # high surrogate: need the low half
            if self.s[self.i : self.i + 2] != "\\u":
                self.err("unpaired surrogate")
            self.i += 1
            lo = hex4()
            if not 0xDC00 <= lo <= 0xDFFF:
                self.err("bad low surrogate")
            v = 0x10000 + ((v - 0xD800) << 10) + (lo - 0xDC00)
        elif 0xDC00 <= v <= 0xDFFF:
            self.err("unpaired surrogate")
        return chr(v)

    def number(self):
        start = self.i
        s = self.s
        if self.i < self.n and s[self.i] == "-":
            self.i += 1
        if self.i >= self.n or s[self.i] not in _DIGITS:
            self.err("bad number")
        if s[self.i] == "0":
            self.i += 1
            if self.i < self.n and s[self.i] in _DIGITS:
                self.err("leading zero")
        else:
            while self.i < self.n and s[self.i] in _DIGITS:
                self.i += 1
        is_float = False
        if self.i < self.n and s[self.i] == ".":
            is_float = True
            self.i += 1
            if self.i >= self.n or s[self.i] not in _DIGITS:
                self.err("bad fraction")
            while self.i < self.n and s[self.i] in _DIGITS:
                self.i += 1
        if self.i < self.n and s[self.i] in "eE":
            is_float = True
            self.i += 1
            if self.i < self.n and s[self.i] in "+-":
                self.i += 1
            if self.i >= self.n or s[self.i] not in _DIGITS:
                self.err("bad exponent")
            while self.i < self.n and s[self.i] in _DIGITS:
                self.i += 1
        text = s[start : self.i]
        if len(text) > MAX_NUMBER_DIGITS:
            self.err("number too long")
        return float(text) if is_float else int(text)


def loads(data: str | bytes, *, reject_duplicate_keys: bool = False):
    if isinstance(data, (bytes, bytearray)):
        data = data.decode("utf-8")
    if len(data) > MAX_LEN:
        raise JsonError("input too large", 0)
    p = _Parser(data, reject_duplicate_keys=reject_duplicate_keys)
    v = p.value(0)
    p.skip_ws()
    if p.i != p.n:
        p.err("trailing data")
    return v


def _esc_str(s: str) -> str:
    out = ['"']
    for c in s:
        if c in _REV_ESC:
            out.append(_REV_ESC[c])
        elif ord(c) < 0x20:
            out.append(f"\\u{ord(c):04x}")
        else:
            out.append(c)
    out.append('"')
    return "".join(out)


def dumps(v, *, sort_keys: bool = False) -> str:
    if v is None:
        return "null"
    if v is True:
        return "true"
    if v is False:
        return "false"
    if isinstance(v, int):
        return str(v)
    if isinstance(v, float):
        if v != v or v in (float("inf"), float("-inf")):
            raise TypeError("non-finite floats are not JSON")
        return repr(v)
    if isinstance(v, str):
        return _esc_str(v)
    if isinstance(v, (list, tuple)):
        return "[" + ",".join(dumps(x, sort_keys=sort_keys) for x in v) + "]"
    if isinstance(v, dict):
        items = sorted(v.items()) if sort_keys else v.items()
        return "{" + ",".join(
            _esc_str(str(k)) + ":" + dumps(x, sort_keys=sort_keys)
            for k, x in items
        ) + "}"
    raise TypeError(f"cannot encode {type(v).__name__}")

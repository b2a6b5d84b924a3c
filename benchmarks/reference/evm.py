"""Plain reference for the corpus's issues: a minimal concrete EVM that
replays an issue's transaction sequence and says whether the issue's
condition occurs. It imports nothing of the program under test.

A sequence (the report's `tx_sequence`) gives the accounts' balances
and code and, step by step, the sender, the value and the calldata.
What it leaves open, the replay fixes to one valid choice: initial
storage the analysis took as free (runtime code) reads 0, a call to an
account without code succeeds with no return data, and block values
are constants. A value that comes from such a choice is tainted. When
a tainted value decides a jump, an address into memory or a storage
key, the replay has left the path the sequence was made for, and a
condition it then misses says nothing.

replay_issue(issue) returns one of:
  "confirmed"      the condition occurred in the last step, at the
                   issue's address, in the code the step was sent to
  "refuted"        it did not, and no tainted value decided anything
  "indeterminate"  it did not, and a tainted value decided something
"""

from .keccak import keccak256

WORD = 1 << 256
MASK = WORD - 1
SIGN = 1 << 255
#: what a tainted read returns, and the block the replay runs in
BLOCK = {"timestamp": 1, "number": 1, "difficulty": 0, "gaslimit": 8000000,
         "coinbase": 0, "chainid": 1, "basefee": 0, "gasprice": 0}
GAS = 8000000
MAX_STEPS = 1 << 20

#: the condition of each kind of issue (SWC id), at its address
_ARITH = {0x01: "ADD", 0x02: "MUL", 0x03: "SUB", 0x0A: "EXP"}
_CALLS = {0xF1, 0xF2, 0xF4, 0xFA}


class Refused(Exception):
    """The replay cannot run this code (an opcode it does not know)."""


def _s(v: int) -> int:
    return v - WORD if v & SIGN else v


class Account:
    def __init__(self, balance=0, code=b"", storage=None, free=False):
        self.balance = balance
        self.code = code
        self.storage = dict(storage or {})
        #: slots whose value is tainted
        self.tainted = set()
        #: initial storage was left free by the analysis
        self.free = free
        self.nonce = 0


class Frame:
    def __init__(self, address, code, caller, value, data, data_taint=False,
                 static=False, depth=0):
        self.address, self.code, self.caller = address, code, caller
        self.value, self.data, self.static = value, data, static
        self.data_taint = data_taint
        self.depth = depth
        self.stack, self.staint = [], []
        self.mem, self.mtaint = bytearray(), bytearray()
        self.returndata, self.rtaint = b"", False
        self.jumpdests = _jumpdests(code)


def _jumpdests(code: bytes) -> set:
    out, i = set(), 0
    while i < len(code):
        op = code[i]
        if op == 0x5B:
            out.add(i)
        i += op - 0x5E if 0x60 <= op <= 0x7F else 1
    return out


def _rlp_create_address(sender: int, nonce: int) -> int:
    def item(b: bytes) -> bytes:
        if len(b) == 1 and b[0] < 0x80:
            return b
        return bytes([0x80 + len(b)]) + b
    n = nonce.to_bytes((nonce.bit_length() + 7) // 8, "big")
    body = item(sender.to_bytes(20, "big")) + item(n)
    return int.from_bytes(keccak256(bytes([0xC0 + len(body)]) + body)[12:],
                          "big")


class Halt(Exception):
    def __init__(self, success, data=b"", taint=False):
        self.success, self.data, self.taint = success, data, taint


class World:
    def __init__(self, accounts: dict):
        self.accounts = accounts
        #: a tainted value decided a jump, a memory address or a key
        self.diverged = False
        self.steps = 0
        #: (address, pc) to watch in the top frame, and what was seen
        self.watch = None
        self.seen = []
        self.origin = 0

    def account(self, a: int) -> Account:
        if a not in self.accounts:
            self.accounts[a] = Account()
        return self.accounts[a]

    def decide(self, *taints) -> None:
        if any(taints):
            self.diverged = True

    # -- one frame -------------------------------------------------------

    def run(self, f: Frame):
        """(success, return data, taint) of a frame."""
        try:
            self._loop(f)
        except Halt as h:
            return h.success, h.data, h.taint
        return True, b"", False

    def _mem(self, f, off, size, *taints):
        self.decide(*taints)
        if size == 0:
            return
        end = off + size
        if end > 1 << 24:
            raise Halt(False)
        if end > len(f.mem):
            grow = (end + 31) // 32 * 32 - len(f.mem)
            f.mem += bytes(grow)
            f.mtaint += bytes(grow)

    def _read(self, f, off, size):
        return bytes(f.mem[off:off + size]), any(f.mtaint[off:off + size])

    def _write(self, f, off, data: bytes, taint: bool):
        f.mem[off:off + len(data)] = data
        f.mtaint[off:off + len(data)] = bytes([taint]) * len(data)

    def _loop(self, f: Frame):
        code, st, tt = f.code, f.stack, f.staint
        pc = 0

        def pop():
            return st.pop(), tt.pop()

        def push(v, t=False):
            st.append(v & MASK)
            tt.append(bool(t))

        while True:
            self.steps += 1
            if self.steps > MAX_STEPS:
                raise Halt(False)
            op = code[pc] if pc < len(code) else 0x00
            if (self.watch is not None and f.depth == 0
                    and pc == self.watch):
                self.seen.append(self._observe(f, op))
            npc = pc + 1
            if 0x60 <= op <= 0x7F:
                n = op - 0x5F
                push(int.from_bytes(code[pc + 1:pc + 1 + n].ljust(n, b"\0"),
                                    "big"))
                npc = pc + 1 + n
            elif op == 0x5F:
                push(0)
            elif 0x80 <= op <= 0x8F:
                i = op - 0x7F
                push(st[-i], tt[-i])
            elif 0x90 <= op <= 0x9F:
                i = op - 0x8E
                st[-1], st[-i] = st[-i], st[-1]
                tt[-1], tt[-i] = tt[-i], tt[-1]
            elif op < 0x20 and op in _BINARY:
                (a, ta), (b, tb) = pop(), pop()
                push(_BINARY[op](a, b), ta or tb)
            elif op in (0x08, 0x09):
                (a, ta), (b, tb), (n, tn) = pop(), pop(), pop()
                v = 0 if n == 0 else ((a + b) if op == 8 else (a * b)) % n
                push(v, ta or tb or tn)
            elif op == 0x15:
                a, ta = pop()
                push(int(a == 0), ta)
            elif op == 0x19:
                a, ta = pop()
                push(~a, ta)
            elif op == 0x00:
                raise Halt(True)
            elif op == 0x20:
                (o, to), (n, tn) = pop(), pop()
                self._mem(f, o, n, to, tn)
                data, t = self._read(f, o, n)
                push(int.from_bytes(keccak256(data), "big"), t)
            elif op == 0x30:
                push(f.address)
            elif op == 0x31:
                a, ta = pop()
                a &= (1 << 160) - 1
                unknown = a not in self.accounts
                push(self.account(a).balance, ta or unknown)
            elif op == 0x32:
                push(self.origin)
            elif op == 0x33:
                push(f.caller)
            elif op == 0x34:
                push(f.value)
            elif op == 0x35:
                o, to = pop()
                self.decide(to)
                push(int.from_bytes(f.data[o:o + 32].ljust(32, b"\0"), "big")
                     if o < len(f.data) else 0, f.data_taint)
            elif op == 0x36:
                push(len(f.data), f.data_taint)
            elif op in (0x37, 0x39, 0x3E):
                (mo, tm), (o, to), (n, tn) = pop(), pop(), pop()
                self._mem(f, mo, n, tm, to, tn)
                src, t = {0x37: (f.data, f.data_taint), 0x39: (code, False),
                          0x3E: (f.returndata, f.rtaint)}[op]
                if op == 0x3E and o + n > len(src):
                    raise Halt(False)
                chunk = src[o:o + n] if o < len(src) else b""
                self._write(f, mo, chunk.ljust(n, b"\0"), t)
            elif op == 0x38:
                push(len(code))
            elif op == 0x3A:
                push(BLOCK["gasprice"], True)
            elif op == 0x3B:
                a, ta = pop()
                a &= (1 << 160) - 1
                unknown = a not in self.accounts
                push(len(self.account(a).code), ta or unknown)
            elif op == 0x3C:
                (a, ta), (mo, tm), (o, to), (n, tn) = pop(), pop(), pop(), pop()
                self._mem(f, mo, n, tm, to, tn)
                c = self.account(a & (1 << 160) - 1).code
                self._write(f, mo, c[o:o + n].ljust(n, b"\0"), ta)
            elif op == 0x3D:
                push(len(f.returndata), f.rtaint)
            elif op == 0x3F:
                a, ta = pop()
                c = self.account(a & (1 << 160) - 1).code
                push(int.from_bytes(keccak256(c), "big") if c else 0, True)
            elif op == 0x40:
                pop()
                push(0, True)
            elif op in (0x41, 0x42, 0x43, 0x44, 0x45, 0x46, 0x48):
                name = {0x41: "coinbase", 0x42: "timestamp", 0x43: "number",
                        0x44: "difficulty", 0x45: "gaslimit",
                        0x46: "chainid", 0x48: "basefee"}[op]
                push(BLOCK[name], True)
            elif op == 0x47:
                push(self.account(f.address).balance)
            elif op == 0x50:
                pop()
            elif op == 0x51:
                o, to = pop()
                self._mem(f, o, 32, to)
                data, t = self._read(f, o, 32)
                push(int.from_bytes(data, "big"), t)
            elif op in (0x52, 0x53):
                (o, to), (v, tv) = pop(), pop()
                n = 32 if op == 0x52 else 1
                self._mem(f, o, n, to)
                self._write(f, o, (v & 0xFF if n == 1 else v).to_bytes(n, "big"),
                            tv)
            elif op == 0x54:
                k, tk = pop()
                self.decide(tk)
                acct = self.account(f.address)
                if k in acct.storage:
                    push(acct.storage[k], k in acct.tainted)
                else:
                    push(0, acct.free)
            elif op == 0x55:
                if f.static:
                    raise Halt(False)
                (k, tk), (v, tv) = pop(), pop()
                self.decide(tk)
                acct = self.account(f.address)
                acct.storage[k] = v
                (acct.tainted.add if tv else acct.tainted.discard)(k)
            elif op == 0x56:
                d, td = pop()
                self.decide(td)
                if d not in f.jumpdests:
                    raise Halt(False)
                npc = d
            elif op == 0x57:
                (d, td), (c, tc) = pop(), pop()
                self.decide(tc, td if c else False)
                if c:
                    if d not in f.jumpdests:
                        raise Halt(False)
                    npc = d
            elif op == 0x58:
                push(pc)
            elif op == 0x59:
                push(len(f.mem))
            elif op == 0x5A:
                push(GAS, True)
            elif op == 0x5B:
                pass
            elif 0xA0 <= op <= 0xA4:
                (o, to), (n, tn) = pop(), pop()
                for _ in range(op - 0xA0):
                    pop()
                self._mem(f, o, n, to, tn)
            elif op in (0xF0, 0xF5):
                (v, tv), (o, to), (n, tn) = pop(), pop(), pop()
                if op == 0xF5:
                    pop()
                self._mem(f, o, n, to, tn)
                init, t = self._read(f, o, n)
                push(self.create(f.address, v, init, f.depth + 1))
                f.returndata, f.rtaint = b"", False
            elif op in _CALLS or op == 0xF2:
                self._call(f, op, pop, push)
            elif op in (0xF3, 0xFD):
                (o, to), (n, tn) = pop(), pop()
                self._mem(f, o, n, to, tn)
                data, t = self._read(f, o, n)
                raise Halt(op == 0xF3, data, t)
            elif op == 0xFE:
                raise Halt(False)
            elif op == 0xFF:
                b, tb = pop()
                self.decide(tb)
                me = self.account(f.address)
                self.account(b & (1 << 160) - 1).balance += me.balance
                me.balance = 0
                raise Halt(True)
            else:
                raise Refused(f"opcode {op:#04x} at {pc}")
            pc = npc

    def _call(self, f, op, pop, push):
        (_, _), (to, tt_) = pop(), pop()
        value, tv = (pop() if op in (0xF1, 0xF2) else (0, False))
        (io, tio), (isz, tis), (oo, too), (osz, tos) = pop(), pop(), pop(), pop()
        self.decide(tt_, tv, tio, tis, too, tos)
        self._mem(f, io, isz)
        self._mem(f, oo, osz)
        data, tdata = self._read(f, io, isz)
        to &= (1 << 160) - 1
        me = self.account(f.address)
        if value and me.balance < value:
            push(0)
            f.returndata, f.rtaint = b"", False
            return
        callee = self.account(to)
        unknown = to not in self.accounts or not callee.code
        if 1 <= to <= 9:
            ok, out, tout = True, _precompile(to, data), tdata or to != 4
        elif not callee.code or f.depth >= 1024:
            ok, out, tout = True, b"", unknown
        else:
            saved = _snapshot(self.accounts)
            if op == 0xF1 and value:
                me.balance -= value
                callee.balance += value
            run_at, code = ((to, callee.code) if op in (0xF1, 0xFA)
                            else (f.address, callee.code))
            caller = f.caller if op == 0xF4 else f.address
            fval = f.value if op == 0xF4 else value
            ok, out, tout = self.run(Frame(
                run_at, code, caller, fval, data, tdata,
                f.static or op == 0xFA, f.depth + 1))
            if not ok:
                self.accounts = saved
            tout = tout or tdata
        if ok and op == 0xF1 and value and not callee.code:
            me.balance -= value
            callee.balance += value
        f.returndata, f.rtaint = out, tout
        push(int(ok), unknown)
        self._write(f, oo, out[:osz], tout)

    def _observe(self, f: Frame, op: int) -> dict:
        """What the condition of an issue at this instruction needs."""
        st = f.stack
        seen = {"op": op}
        if op in _ARITH and len(st) >= 2:
            a, b = st[-1], st[-2]
            seen["overflow"] = {0x01: a + b >= WORD, 0x02: a * b >= WORD,
                                0x03: a < b, 0x0A: pow(a, b) >= WORD
                                if b < 512 else a > 1}[op]
        if op in (0xF1, 0xF2) and len(st) >= 3:
            seen["value"] = st[-3]
        return seen

    # -- transactions ----------------------------------------------------

    def create(self, sender: int, value: int, init: bytes, depth: int) -> int:
        s = self.account(sender)
        addr = _rlp_create_address(sender, s.nonce)
        s.nonce += 1
        saved = _snapshot(self.accounts)
        new = self.account(addr)
        new.balance += value
        s.balance -= value
        ok, out, _ = self.run(Frame(addr, init, sender, value, b"",
                                    depth=depth))
        if not ok:
            self.accounts = saved
            return 0
        self.accounts[addr].code = out
        return addr


def _snapshot(accounts: dict) -> dict:
    out = {}
    for a, acct in accounts.items():
        c = Account(acct.balance, acct.code, acct.storage, acct.free)
        c.tainted, c.nonce = set(acct.tainted), acct.nonce
        out[a] = c
    return out


def _precompile(to: int, data: bytes) -> bytes:
    if to == 2:
        import hashlib

        return hashlib.sha256(data).digest()
    if to == 4:
        return data
    return b""


def _exp(a, b):
    return pow(a, b, WORD)


def _div(a, b):
    return a // b if b else 0


def _sdiv(a, b):
    if b == 0:
        return 0
    a, b = _s(a), _s(b)
    q = abs(a) // abs(b)
    return -q if (a < 0) != (b < 0) else q


def _mod(a, b):
    return a % b if b else 0


def _smod(a, b):
    if b == 0:
        return 0
    a, b = _s(a), _s(b)
    r = abs(a) % abs(b)
    return -r if a < 0 else r


def _signextend(b, x):
    if b >= 31:
        return x
    bit = 8 * b + 7
    return x | (MASK << bit) if x >> bit & 1 else x & ((1 << bit) - 1)


def _byte(i, x):
    return x >> (8 * (31 - i)) & 0xFF if i < 32 else 0


def _sar(n, x):
    return (_s(x) >> n if n < 256 else (-1 if x & SIGN else 0))


_BINARY = {
    0x01: lambda a, b: a + b, 0x02: lambda a, b: a * b,
    0x03: lambda a, b: a - b, 0x04: _div, 0x05: _sdiv, 0x06: _mod,
    0x07: _smod, 0x0A: _exp, 0x0B: _signextend,
    0x10: lambda a, b: int(a < b), 0x11: lambda a, b: int(a > b),
    0x12: lambda a, b: int(_s(a) < _s(b)),
    0x13: lambda a, b: int(_s(a) > _s(b)),
    0x14: lambda a, b: int(a == b), 0x16: lambda a, b: a & b,
    0x17: lambda a, b: a | b, 0x18: lambda a, b: a ^ b, 0x1A: _byte,
    0x1B: lambda n, x: x << n if n < 256 else 0,
    0x1C: lambda n, x: x >> n if n < 256 else 0, 0x1D: _sar,
}


def _int(h) -> int:
    return int(h, 16) if isinstance(h, str) and h not in ("", "0x") else 0


def _bytes(h: str) -> bytes:
    h = h[2:] if h.startswith("0x") else h
    return bytes.fromhex(h)


def world_of(seq: dict) -> World:
    """The world of a sequence's initial state. Storage that it gives
    as "{}" (nothing concrete) is taken as left free where the account
    has code, as the analysis of runtime code leaves it."""
    accounts = {}
    for a, acct in seq["initialState"]["accounts"].items():
        code = _bytes(acct.get("code") or "0x")
        storage = acct.get("storage")
        concrete = storage if isinstance(storage, dict) else {}
        accounts[_int(a)] = Account(
            _int(acct.get("balance")), code,
            {_int(k): _int(v) for k, v in concrete.items()},
            free=bool(code) and not concrete)
    return World(accounts)


def replay_issue(issue: dict) -> str:
    seq = issue.get("tx_sequence") or {}
    steps = seq.get("steps") or []
    if not steps:
        return "refuted"
    w = world_of(seq)
    sswc = issue.get("swc-id")
    try:
        for i, step in enumerate(steps):
            origin = _int(step["origin"])
            w.origin = origin
            value = _int(step.get("value"))
            last = i == len(steps) - 1
            if last:
                w.watch, w.seen = issue["address"], []
            sender = w.account(origin)
            if value > sender.balance:
                sender.balance = value
            if not step.get("address"):
                init = _bytes(step["input"])
                w.watch = issue["address"] if last else None
                _create_top(w, origin, value, init)
            else:
                to = _int(step["address"])
                callee = w.account(to)
                saved = _snapshot(w.accounts)
                sender.balance -= value
                callee.balance += value
                ok, _, _ = w.run(Frame(to, callee.code, origin, value,
                                       _bytes(step.get("input") or "0x")))
                if not ok:
                    w.accounts = saved
    except Refused:
        return "indeterminate"
    if any(_holds(sswc, s) for s in w.seen):
        return "confirmed"
    return "indeterminate" if w.diverged else "refuted"


def _create_top(w: World, origin: int, value: int, init: bytes) -> None:
    """A creation step: the init code runs in a top frame of its own,
    so that an issue in the constructor is watched there."""
    s = w.account(origin)
    addr = _rlp_create_address(origin, s.nonce)
    s.nonce += 1
    saved = _snapshot(w.accounts)
    new = w.account(addr)
    new.balance += value
    s.balance -= value
    ok, out, _ = w.run(Frame(addr, init, origin, value, b""))
    if ok:
        w.accounts[addr].code = out
    else:
        w.accounts = saved


def _holds(swc: str, seen: dict) -> bool:
    if swc == "101":
        return bool(seen.get("overflow"))
    if swc == "105":
        return bool(seen.get("value"))
    if swc == "106":
        return seen["op"] == 0xFF
    if swc in ("104", "107"):
        return seen["op"] in _CALLS
    if swc == "112":
        return seen["op"] == 0xF4
    return True

"""The plain reference: merge-tree semantics for a sequenced observer.

Imports nothing of the program. One flat list of segments per document,
walked op by op, following the published merge-tree rules
(FluidFramework packages/dds/merge-tree, mergeTree.ts):

- an op resolves its positions in its own view (refSeq, client): a
  segment counts if it was inserted at or below refSeq or by the same
  client, and it does not count once removed at or below refSeq or by
  that client;
- an insert lands before the first segment at which its position is
  reached, including segments its view cannot see: the later-sequenced
  insert lands leftmost (``breakTie``);
- remove and annotate split at the range ends and touch the segments
  visible in the op's view; a segment keeps its first removal's seq;
- annotate sets a key, a None value deletes it; insert-time props set
  the new segment's keys.

Tombstones are never dropped here: a segment removed below every
client's view is invisible in every later view, so keeping it changes
no text, no marker position and no property.

``SequentialDoc`` is the same semantics for single-writer streams whose
refSeq is always the previous seq: every view is the tip, so a string
splice is the whole rule.
"""
from __future__ import annotations


class Seg:
    __slots__ = ("text", "seq", "client", "rseq", "removers", "props")

    def __init__(self, text, seq, client, props=None):
        self.text = text          # None for a marker (length 1)
        self.seq = seq
        self.client = client
        self.rseq = None          # seq of the first removal
        self.removers = ()        # clients that removed it
        self.props = props or {}

    @property
    def length(self) -> int:
        return 1 if self.text is None else len(self.text)

    def split(self, off: int) -> "Seg":
        tail = Seg(self.text[off:], self.seq, self.client, dict(self.props))
        tail.rseq, tail.removers = self.rseq, self.removers
        self.text = self.text[:off]
        return tail


class MergeDoc:
    """One document as a sequenced observer applies it."""

    def __init__(self):
        self.segs: list[Seg] = []

    @staticmethod
    def visible(seg: Seg, ref: int, client: int) -> int:
        if seg.rseq is not None and (seg.rseq <= ref
                                     or client in seg.removers):
            return 0
        if seg.client == client or seg.seq <= ref:
            return seg.length
        return 0

    def view_length(self, ref: int, client: int) -> int:
        return sum(self.visible(s, ref, client) for s in self.segs)

    def _boundary(self, pos: int, ref: int, client: int) -> None:
        rem = pos
        for i, seg in enumerate(self.segs):
            n = self.visible(seg, ref, client)
            if rem < n:
                if rem > 0:
                    self.segs.insert(i + 1, seg.split(rem))
                return
            rem -= n

    def _range(self, start: int, end: int, ref: int, client: int):
        if not 0 <= start < end <= self.view_length(ref, client):
            raise ValueError(f"range [{start}, {end}) outside the view")
        self._boundary(start, ref, client)
        self._boundary(end, ref, client)
        acc = 0
        for seg in self.segs:
            if acc >= end:
                break
            n = self.visible(seg, ref, client)
            if n and acc >= start:
                yield seg
            acc += n

    def insert(self, pos, text, props, seq, ref, client) -> None:
        seg = Seg(text, seq, client,
                  {k: v for k, v in (props or {}).items() if v is not None})
        rem = pos
        for i, cur in enumerate(self.segs):
            if rem == 0:
                self.segs.insert(i, seg)
                return
            n = self.visible(cur, ref, client)
            if rem < n:
                self.segs.insert(i + 1, cur.split(rem))
                self.segs.insert(i + 1, seg)
                return
            rem -= n
        if rem:
            raise ValueError(f"insert at {pos} beyond the view")
        self.segs.append(seg)

    def remove(self, start, end, seq, ref, client) -> None:
        for seg in list(self._range(start, end, ref, client)):
            if seg.rseq is None:
                seg.rseq, seg.removers = seq, (client,)
            else:
                seg.removers = seg.removers + (client,)

    def annotate(self, start, end, props, seq, ref, client) -> None:
        for seg in list(self._range(start, end, ref, client)):
            for key, value in props.items():
                if value is None:
                    seg.props.pop(key, None)
                else:
                    seg.props[key] = value

    def apply(self, op, seq, ref, client) -> None:
        """``op``: (kind, pos1, pos2, text, props) as ``ops.py`` makes
        them; kind 'ins' (text None = marker), 'rem' or 'ann'."""
        kind, p1, p2, text, props = op
        if kind == "ins":
            self.insert(p1, text, props, seq, ref, client)
        elif kind == "rem":
            self.remove(p1, p2, seq, ref, client)
        else:
            self.annotate(p1, p2, props, seq, ref, client)

    def text(self) -> str:
        return "".join(s.text for s in self.segs
                       if s.rseq is None and s.text is not None)

    def signature(self) -> list:
        """Per position: (char, or 'M' for a marker, props dict)."""
        out = []
        for s in self.segs:
            if s.rseq is not None:
                continue
            if s.text is None:
                out.append(("M", s.props))
            else:
                out.extend((ch, s.props) for ch in s.text)
        return out


class SequentialDoc:
    """Single-writer stream, every op at the tip: a string splice."""

    def __init__(self):
        self.chars: list[str] = []

    def apply(self, op, seq=None, ref=None, client=None) -> None:
        kind, p1, p2, text, _props = op
        if kind == "ins":
            if not 0 <= p1 <= len(self.chars):
                raise ValueError(f"insert at {p1} beyond the text")
            self.chars[p1:p1] = list(text)
        elif kind == "rem":
            if not 0 <= p1 < p2 <= len(self.chars):
                raise ValueError(f"remove [{p1}, {p2}) outside the text")
            del self.chars[p1:p2]
        else:
            raise ValueError("sequential replay carries no annotate")

    def text(self) -> str:
        return "".join(self.chars)

    def signature(self) -> list:
        return [(ch, {}) for ch in self.chars]


def props_agree(ref_sig: list, served_sig) -> bool:
    """The served signature is per position (char | 'M', one interned
    id per property channel, 0 = unset). The ids and the channel of
    each key are the program's own interning, so compare up to a
    renaming: there must be an injective map key -> channel under which
    every key's values map one-to-one onto that channel's ids, and
    every channel no key maps to is unset everywhere."""
    if len(ref_sig) != len(served_sig):
        return False
    if any(r[0] != s[0] for r, s in zip(ref_sig, served_sig)):
        return False
    keys = sorted({k for _, props in ref_sig for k in props})
    n_chan = len(served_sig[0][1]) if served_sig else 0
    fits = {}
    for key in keys:
        fits[key] = [c for c in range(n_chan)
                     if _channel_matches(ref_sig, served_sig, key, c)]
    return _assign(keys, fits, set(), ref_sig, served_sig, n_chan)


def _channel_matches(ref_sig, served_sig, key, chan) -> bool:
    fwd, back = {}, {}
    for (_, props), (_, ids) in zip(ref_sig, served_sig):
        value, vid = props.get(key), ids[chan]
        if (value is None) != (vid == 0):
            return False
        if value is None:
            continue
        if fwd.setdefault(repr(value), vid) != vid:
            return False
        if back.setdefault(vid, repr(value)) != repr(value):
            return False
    return True


def _assign(keys, fits, used, ref_sig, served_sig, n_chan) -> bool:
    if not keys:
        return all(ids[c] == 0 for _, ids in served_sig
                   for c in range(n_chan) if c not in used)
    for chan in fits[keys[0]]:
        if chan not in used and _assign(keys[1:], fits, used | {chan},
                                        ref_sig, served_sig, n_chan):
            return True
    return False

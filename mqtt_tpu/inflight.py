"""Per-client inflight (QoS>0) message map plus MQTT v5 send/receive flow
quotas.

Behavioral parity with reference ``inflight.go:16-156``.
"""

from __future__ import annotations

from typing import Any, Optional

from .packets import Packet
from .utils.locked import InstrumentedLock

# what Inflight.begin answers in place of a packet id (1..65535): an
# entry was stored exactly where the answer is BEGIN_PARKED or above
BEGIN_PARKED = -1  # stored, but the send quota is spent: nothing is written
BEGIN_FULL = -2  # the session holds maximum_inflight entries: nothing stored
BEGIN_EXHAUSTED = -3  # every packet id is in flight: nothing stored


class Inflight:
    """Inflight packets keyed on packet id, with send/receive quota counters
    used for v5 flow control (inflight.go:16-23)."""

    def __init__(self) -> None:
        self._lock = InstrumentedLock("inflight", rlock=True)
        self.internal: dict[int, Packet] = {}
        self.receive_quota = 0  # remaining inbound qos quota
        self.send_quota = 0  # remaining outbound qos quota
        self.maximum_receive_quota = 0
        self.maximum_send_quota = 0
        # while the session is in the broker's registry: the set of
        # sessions holding entries (``clients.SessionsDue.inflight``) and
        # this map's session, which is in the set exactly while the map
        # holds an entry; the housekeeping tick's expiry pass walks it
        self._due: Optional[set] = None
        self._owner: Any = None

    def watch(self, due: Optional[set], owner: Any) -> None:
        """Keep ``owner`` in ``due`` while the map holds an entry (None:
        stop)."""
        with self._lock:
            self._due, self._owner = due, owner
            if due is not None and self.internal:
                due.add(owner)

    def set(self, m: Packet) -> bool:
        """Add or update by packet id; True if it was new (inflight.go:33)."""
        with self._lock:
            internal = self.internal
            existed = m.packet_id in internal
            internal[m.packet_id] = m
            if len(internal) == 1 and self._due is not None:
                self._due.add(self._owner)
            return not existed

    def set_bulk(self, packets: list[Packet]) -> int:
        """Batched :meth:`set` for durable-session restore
        (staging.bulk_inflight): one lock acquisition per chunk instead
        of one per packet. Returns how many ids were new."""
        with self._lock:
            new = 0
            for m in packets:
                if m.packet_id not in self.internal:
                    new += 1
                self.internal[m.packet_id] = m
            if self.internal and self._due is not None:
                self._due.add(self._owner)
            return new

    def _free_id(self, start: int, maximum: int) -> int:
        """The packet-id walk (clients.go:274-299), the lock held by the
        caller: the first id after ``start`` whose low 16 bits are not in
        flight, wrapping to 1 past ``maximum``; -1 once the walk comes
        round to ``start``."""
        internal = self.internal
        i = start + 1
        if i <= maximum and internal.get(i & 0xFFFF) is None:
            return i  # the walk's first step, as a session mostly finds it
        i = start
        overflowed = False
        while True:
            if overflowed and i == start:
                return -1
            if i >= maximum:
                overflowed = True
                i = 0
                continue
            i += 1
            if internal.get(i & 0xFFFF) is None:
                return i

    def next_id(self, start: int, maximum: int) -> int:
        """:meth:`_free_id` under one acquisition of the lock."""
        with self._lock:
            return self._free_id(start, maximum)

    def begin(self, state, pk: Packet, cap: int, maximum: int) -> int:
        """One outbound QoS>0 delivery's bookkeeping under ONE acquisition
        of the lock, in the order the reference takes it: the cap on
        entries (``cap``: maximum_inflight), a packet id from the walk
        of :meth:`_free_id` starting at ``state.packet_id`` (the
        session's cursor, moved to the id), ``pk`` stored under it with
        ``pk.packet_id`` set, and the send quota spent. The entry is
        always new: the walk found its id free under the same hold.

        Returns the packet id, or BEGIN_FULL / BEGIN_EXHAUSTED with
        nothing stored, or BEGIN_PARKED where the quota was already 0
        under a positive maximum: the entry waits for quota, and the
        caller marks it for resend (``expiry = -1``) once its
        ``on_qos_publish`` hooks have seen it."""
        with self._lock:
            return self._begin_locked(state, pk, cap, maximum)

    def begin_run(self, state, entries: list, cap: int, maximum: int) -> list:
        """:meth:`begin` for each of ``entries`` in order, under ONE
        acquisition of the lock: one socket's QoS>0 deliveries of a
        completion slice (``server._settle_run``). Returns one answer an
        entry, as :meth:`begin` answers it."""
        with self._lock:
            begin = self._begin_locked
            return [begin(state, pk, cap, maximum) for pk in entries]

    def _begin_locked(self, state, pk: Packet, cap: int, maximum: int) -> int:
        """:meth:`begin`'s bookkeeping, the lock held by the caller."""
        internal = self.internal
        if len(internal) >= cap:
            return BEGIN_FULL
        i = self._free_id(state.packet_id, maximum)
        if i < 0:
            return BEGIN_EXHAUSTED
        state.packet_id = i
        pid = pk.packet_id = i & 0xFFFF  # [MQTT-2.2.1-4]
        internal[pid] = pk  # [MQTT-4.3.2-3] [MQTT-4.3.3-3]
        if len(internal) == 1 and self._due is not None:
            self._due.add(self._owner)
        quota = self.send_quota
        if quota > 0:
            self.send_quota = quota - 1
        elif quota == 0 and self.maximum_send_quota > 0:
            return BEGIN_PARKED
        return pid

    def get(self, id_: int) -> Optional[Packet]:
        with self._lock:
            return self.internal.get(id_)

    def __len__(self) -> int:
        with self._lock:
            return len(self.internal)

    def clone(self) -> "Inflight":
        """Copy for session takeover (inflight.go:63-71)."""
        c = Inflight()
        with self._lock:
            c.internal = dict(self.internal)
        return c

    def get_all(self, immediate: bool) -> list[Packet]:
        """All inflight messages ordered by created time; when ``immediate``,
        only packets flagged for immediate resend (expiry < 0, set when the
        send quota was exhausted) (inflight.go:74-90)."""
        with self._lock:
            m = [v for v in self.internal.values() if not immediate or v.expiry < 0]
        # reference sorts on uint16(Created) — preserved for identical order
        m.sort(key=lambda pk: pk.created & 0xFFFF)
        return m

    def next_immediate(self) -> Optional[Packet]:
        """The next quota-starved packet to resend (inflight.go:95-105)."""
        m = self.get_all(True)
        return m[0] if m else None

    def delete(self, id_: int) -> bool:
        with self._lock:
            internal = self.internal
            if internal.pop(id_, None) is None:
                return False
            if not internal and self._due is not None:
                self._due.discard(self._owner)
            return True

    def acknowledge(self, ids: list[int]) -> int:
        """A run of acknowledged packet ids under ONE acquisition of the
        lock: every id that is in flight leaves the map (an unknown or a
        repeated one is passed over) and the send quota rises by their
        number within its maximum, which is what ``get``, ``delete``
        and ``increase_send_quota`` an id come to. Returns how many
        left.

        -1, and nothing touched, while an entry waits for send quota
        (``expiry < 0``) and the quota is or can become positive: each
        single ack would then be followed by that entry's resend (the
        server's quota drain), so the ids go one at a time."""
        with self._lock:
            internal = self.internal
            if self.send_quota > 0 or self.maximum_send_quota > 0:
                for m in internal.values():
                    if m.expiry < 0:
                        return -1
            removed = 0
            for id_ in ids:
                if internal.pop(id_, None) is not None:
                    removed += 1
            if not internal and self._due is not None:
                self._due.discard(self._owner)
            if self.send_quota < self.maximum_send_quota:
                self.send_quota = min(
                    self.send_quota + removed, self.maximum_send_quota
                )
            return removed

    # -- flow-control quotas (inflight.go:119-156) -------------------------

    def decrease_receive_quota(self) -> None:
        if self.receive_quota > 0:
            self.receive_quota -= 1

    def increase_receive_quota(self) -> None:
        if self.receive_quota < self.maximum_receive_quota:
            self.receive_quota += 1

    def reset_receive_quota(self, n: int) -> None:
        self.receive_quota = n
        self.maximum_receive_quota = n

    def decrease_send_quota(self) -> None:
        if self.send_quota > 0:
            self.send_quota -= 1

    def increase_send_quota(self) -> None:
        if self.send_quota < self.maximum_send_quota:
            self.send_quota += 1

    def reset_send_quota(self, n: int) -> None:
        self.send_quota = n
        self.maximum_send_quota = n

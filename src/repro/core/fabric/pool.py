"""Pooled memory: many hosts sharing many devices through the fabric.

The pooling story CXL 2.0+ sells: a rack of memory devices behind a switch,
carved up or interleaved across hosts.  :class:`PoolAddressMapper` turns a
host-physical address into ``(device_index, device_local_address)``;
:class:`MemoryPool` binds the mapper + fabric + devices and hands out
per-host :class:`HostPortView`\\ s — each a plain ``MemDevice``, so existing
drivers (``TraceDriver``, ``MultiHostDriver``) run against pooled memory
unchanged while per-host stats accumulate on the view.

Mapping modes:

``interleave``  frames of ``granularity`` bytes round-robin across devices
                (spreads one host's bandwidth over all devices)
``segment``     contiguous ``segment_bytes`` slabs, one device per slab
                (capacity pooling: each slab is a private region)

Logical-device partitions (``ld_bytes``): a CXL 2.0 Multi-Logical Device
splits a device into up to 16 logical devices (LDs), each bound to one host
with a device-physical range of its own, so hosts share no data.  A pool
built with ``ld_bytes`` hands out views in which view ``k`` owns LD ``k``:
host addresses ``[0, ld_bytes)`` of that view map to pool addresses
``k * ld_bytes + addr`` (then through the mapper), and an access reaching
``ld_bytes`` is refused with :class:`LogicalDeviceRangeError`.  Without
``ld_bytes`` every view maps the one global pool address space.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

from repro.core.devices import MemDevice
from repro.core.fabric.fabric import Fabric, LINE_BYTES

DEFAULT_GRANULARITY = 4096   # one flash/DRAM-cache page
LDS_PER_DEVICE = 16          # CXL 2.0: an MLD holds at most 16 LDs


class LogicalDeviceRangeError(ValueError):
    """An access that reaches past the end of its host's logical device."""


def ld_range_error(host: str, ld: int, addr: int, size: int,
                   ld_bytes: int) -> LogicalDeviceRangeError:
    """The one refusal both the interpreted view and the fused replay raise
    for an access outside ``[0, ld_bytes)``."""
    return LogicalDeviceRangeError(
        f"host {host!r}: access {addr:#x}+{size} outside its logical "
        f"device LD{ld} of {ld_bytes:#x} bytes")


@dataclass(frozen=True)
class PoolAddressMapper:
    num_devices: int
    mode: str = "interleave"              # 'interleave' | 'segment'
    granularity: int = DEFAULT_GRANULARITY
    segment_bytes: int = 1 << 30          # per-device slab in 'segment' mode

    def __post_init__(self) -> None:
        if self.num_devices < 1:
            raise ValueError("pool needs at least one device")
        if self.mode not in ("interleave", "segment"):
            raise ValueError(f"unknown pool mode {self.mode!r}")
        if self.granularity < 1 or self.segment_bytes < 1:
            raise ValueError("granularity/segment_bytes must be positive")

    def map(self, addr: int) -> Tuple[int, int]:
        """Global pool address -> ``(device_index, device_local_addr)``."""
        if self.mode == "interleave":
            frame, off = divmod(addr, self.granularity)
            dev, local_frame = frame % self.num_devices, frame // self.num_devices
            return dev, local_frame * self.granularity + off
        dev, local = divmod(addr, self.segment_bytes)
        if dev >= self.num_devices:
            raise ValueError(
                f"address {addr:#x} beyond pool capacity "
                f"({self.num_devices} x {self.segment_bytes:#x})")
        return dev, local


class MemoryPool:
    """Devices mounted at fabric nodes + an address mapper across them."""

    def __init__(self, fabric: Fabric, devices: Dict[str, MemDevice],
                 mapper: Optional[PoolAddressMapper] = None,
                 detach_links: bool = True,
                 ld_bytes: Optional[int] = None) -> None:
        if not devices:
            raise ValueError("pool needs at least one device")
        if ld_bytes is not None and ld_bytes < 1:
            raise ValueError(f"ld_bytes must be positive, got {ld_bytes}")
        for node in devices:
            if node not in fabric.topology.kinds:
                raise ValueError(f"unknown fabric node {node!r}")
        self.mapper = mapper or PoolAddressMapper(num_devices=len(devices))
        if self.mapper.num_devices != len(devices):
            raise ValueError("mapper.num_devices != number of pool devices")
        self.fabric = fabric
        self.ld_bytes = ld_bytes
        self.max_lds = LDS_PER_DEVICE * len(devices)
        self._next_ld = 0
        self.device_nodes: List[str] = sorted(devices)
        # Detach only after all validation: a failed construction must not
        # leave the caller's devices silently mutated (NullLink'd).
        self.devices: List[MemDevice] = [
            devices[n].detach_link() if detach_links else devices[n]
            for n in self.device_nodes]

    def view(self, host: str) -> "HostPortView":
        """This host's window onto the pool (a normal ``MemDevice``); in an
        LD pool the next free logical device, in the order views are made."""
        ld = None
        if self.ld_bytes is not None:
            if self._next_ld >= self.max_lds:
                raise ValueError(
                    f"pool of {len(self.devices)} device(s) holds at most "
                    f"{self.max_lds} logical devices")
            ld = self._next_ld
        view = HostPortView(self, host, ld)
        if ld is not None:
            self._next_ld += 1
        return view

    def views(self, hosts: Sequence[str]) -> List["HostPortView"]:
        return [self.view(h) for h in hosts]


class HostPortView(MemDevice):
    """One host's port into a :class:`MemoryPool`.

    ``service`` routes each access through the fabric from this host to the
    device the mapper selects; contention with other hosts emerges from the
    shared port and device busy-until state.  Stats on this object are
    per-host; stats on the pooled devices are aggregate.  In an LD pool
    the view owns logical device ``ld``: pool addresses ``ld_base`` to
    ``ld_base + ld_bytes``.
    """

    def __init__(self, pool: MemoryPool, host: str,
                 ld: Optional[int] = None) -> None:
        # Inherit an engine so the event-driven path (access/access_flit)
        # works; pooled devices share one engine in full-system mode.
        super().__init__(pool.devices[0].engine)
        if host not in pool.fabric.topology.kinds:
            raise ValueError(f"unknown host node {host!r}")
        self.pool = pool
        self.host = host
        self.name = f"pool-view:{host}"
        self.ld = ld
        self.ld_bytes = pool.ld_bytes if ld is not None else None
        self.ld_base = ld * pool.ld_bytes if ld is not None else 0
        for node in pool.device_nodes:          # fail fast if unroutable
            pool.fabric.routing.path(host, node)

    def pool_address(self, addr: int, size: int = LINE_BYTES) -> int:
        """This host's address in the pool address space (LD base added);
        refuses an access outside the view's logical device."""
        if self.ld_bytes is None:
            return addr
        if addr < 0 or addr + size > self.ld_bytes:
            raise ld_range_error(self.host, self.ld, addr, size,
                                 self.ld_bytes)
        return self.ld_base + addr

    def service(self, now: int, addr: int, size: int, write: bool,
                posted: bool = False) -> int:
        pool_addr = self.pool_address(addr, size)
        self._count(size, write)
        dev_idx, local = self.pool.mapper.map(pool_addr)
        node = self.pool.device_nodes[dev_idx]
        # ECMP flow key: the device-local line address — the same value the
        # fused replay hashes host-side after applying the pool mapper.
        t, floor = self.pool.fabric.traverse_qos(now, self.host, node, size,
                                                 line_addr=local // LINE_BYTES)
        done = self.pool.devices[dev_idx].service(t, local, size, write,
                                                  posted)
        return max(done, floor)


def ld_table(targets: Sequence) -> Optional[List[Dict[str, int]]]:
    """Each host's logical device, ``{"ld", "base", "bytes"}`` in host
    order, when ``targets`` are views of an LD pool; else ``None``."""
    if not (isinstance(targets[0], HostPortView)
            and targets[0].ld_bytes is not None):
        return None
    return [{"ld": t.ld, "base": t.ld_base, "bytes": t.ld_bytes}
            for t in targets]

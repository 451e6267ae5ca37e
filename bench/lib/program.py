"""The system under test, built from a configuration file.

Lanes call these to turn a ``bench/configs`` file into the program's own
device and fabric objects, so the configuration is the one that runs.  The
FTL takes its over-provisioning and GC watermark from the program's
defaults; :func:`device` refuses a program whose FTL disagrees with the
file.
"""

from __future__ import annotations


def device(config: dict):
    """One fresh cached CXL-SSD as the configuration states it."""
    from repro.core.cache.dram_cache import DRAMCacheConfig
    from repro.core.devices import CachedCXLSSDDevice, CXLLink
    from repro.core.ssd.hil import SSDConfig
    from repro.core.ssd.pal import NANDTiming

    dev = config["device"]
    if dev["kind"] != "cxl-ssd-cache":
        raise ValueError(f"device kind {dev['kind']!r} has no builder here")
    ssd = dev["ssd"]
    built = CachedCXLSSDDevice(
        ssd_cfg=SSDConfig(
            capacity_bytes=ssd["capacity_bytes"],
            page_bytes=ssd["page_bytes"], channels=ssd["channels"],
            dies_per_channel=ssd["dies_per_channel"],
            pages_per_block=ssd["pages_per_block"],
            timing=NANDTiming(**ssd["nand"]),
            hil_overhead_ns=ssd["hil_overhead_ns"]),
        cache_cfg=DRAMCacheConfig(**dev["cache"]),
        link=CXLLink(**dev["link"]))
    ftl = built.hil.ftl
    logical = ssd["capacity_bytes"] // ssd["page_bytes"]
    blocks = max(4, -(-int(logical * (1 + ssd["op_ratio"]))
                      // ssd["pages_per_block"]))
    watermark = max(2, int(blocks * ssd["gc_watermark"]))
    if (ftl.num_blocks, ftl.gc_watermark_blocks) != (blocks, watermark):
        raise ValueError(
            f"program FTL has {ftl.num_blocks} blocks, watermark "
            f"{ftl.gc_watermark_blocks}; the configuration states "
            f"{blocks}, {watermark}")
    return built


def targets(config: dict):
    """The host targets: the device itself on a direct link, or one fabric
    mount per host (host i mounts device i)."""
    if config["attach"] == "direct":
        return [device(config)]
    from repro.core.fabric import Fabric

    fab = config["fabric"]
    h = config["hosts"]
    fabric = Fabric.build(fab["kind"], num_hosts=h, num_devices=h,
                          num_leaves=fab["num_leaves"], bw_gbps=fab["bw_gbps"],
                          forward_ns=fab["forward_ns"],
                          rt_extra_ns=fab["rt_extra_ns"])
    return [fabric.mount(f"h{i}", f"d{i}", device(config)) for i in range(h)]

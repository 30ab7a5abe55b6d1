"""The traffic generator's arithmetic: what a device reports at a tick,
and how a tick goes on the wire. Imports numpy only: the feeder child
(which must never touch JAX) and the harness (which regenerates the same
frames for the reference once the window has closed) both use it.

A copy, by design, of `sitewhere_tpu/sim/simulator.py`'s signal model
(base + amplitude * sin + noise, spikes at a fixed share of events) and
of `MeasurementBatch.encode()`'s SWB1 layout: the yardstick does not
move when the program's simulator does. Unlike the simulator, a tick's
noise is drawn from a generator keyed by (seed, tenant, tick), so any
tick can be made again on its own, in any process.

A *tick* is one reading from every device of a tenant. A *frame* is what
one gateway sends at once: `frame_devices` devices with consecutive ids,
one reading each. A fleet of `devices` is `devices / frame_devices`
gateways that report one after another, so frame `f` carries slice
`f % slices` of tick `f // slices`; with `frame_devices = devices` a
frame is a whole tick.
"""

from __future__ import annotations

import struct

import numpy as np

TICK_S = 60.0            # event time between two ticks of one device
_HEADER = struct.Struct("<4sBBI")   # magic, msg_type, flags, count
_MAGIC, _MSG_MEASUREMENTS = b"SWB1", 1

# the signal model's constants (SimConfig's defaults)
BASE_MEAN, BASE_SPREAD = 21.0, 3.0
AMPLITUDE, PERIOD_S, NOISE_STD = 2.0, 3600.0, 0.15


def seed32(seed: int) -> int:
    """--seed is any whole number up to a little over 2**31; numpy takes
    any non-negative int, JAX's PRNGKey wants it to fit 32 bits."""
    return int(seed) % (2 ** 32)


class Fleet:
    """One tenant's devices: each one's base level, amplitude, period and
    phase, drawn once from (seed, tenant)."""

    def __init__(self, seed: int, tenant: int, devices: int,
                 anomaly_rate: float = 0.0, anomaly_magnitude: float = 0.0,
                 frame_devices: int | None = None):
        rng = np.random.default_rng([seed32(seed), tenant, 0xF1EE7])
        n = self.devices = int(devices)
        self.frame_devices = int(frame_devices or n)
        if n % self.frame_devices:
            raise ValueError(f"{n} devices are no whole number of frames "
                             f"of {self.frame_devices}")
        self.slices = n // self.frame_devices
        self._tick = (None, None)      # the tick whose frames are being sent
        self.seed, self.tenant = seed32(seed), int(tenant)
        self.base = (BASE_MEAN + BASE_SPREAD
                     * rng.standard_normal(n)).astype(np.float32)
        self.phase = rng.uniform(0, 2 * np.pi, n).astype(np.float32)
        self.period = (PERIOD_S * rng.uniform(0.8, 1.25, n)).astype(np.float32)
        self.amp = (AMPLITUDE * rng.uniform(0.5, 1.5, n)).astype(np.float32)
        self.anomaly_rate = float(anomaly_rate)
        self.anomaly_magnitude = float(anomaly_magnitude)
        self.device_index = np.arange(n, dtype=np.uint32)
        self._mtype = np.zeros(self.frame_devices, np.uint16).tobytes()
        self._dev_bytes = self.device_index.tobytes()

    def values(self, tick: int, spikes: bool = True) -> np.ndarray:
        """float32 [devices]: every device's reading at tick `tick`
        (event time `tick * TICK_S`)."""
        rng = np.random.default_rng([self.seed, self.tenant, int(tick)])
        t = np.float32(tick * TICK_S)
        noise = rng.standard_normal(self.devices, np.float32)
        v = (self.base + self.amp * np.sin(
            np.float32(2 * np.pi) * (t / self.period) + self.phase)
            + np.float32(NOISE_STD) * noise)
        if spikes and self.anomaly_rate > 0:
            hit = rng.random(self.devices, np.float32) < self.anomaly_rate
            sign = np.where(rng.random(self.devices, np.float32) < 0.5,
                            np.float32(-1), np.float32(1))
            v = v + hit * sign * np.float32(self.anomaly_magnitude)
        return v.astype(np.float32)

    def frame(self, index: int) -> bytes:
        """Frame `index` as one u32-LE length-prefixed SWB1 measurement
        frame: slice `index % slices` of tick `index // slices`, one
        event from each of its devices, ids ascending."""
        tick, part = divmod(int(index), self.slices)
        if self._tick[0] != tick:
            self._tick = (tick, self.values(tick))
        n = self.frame_devices
        lo = part * n
        payload = b"".join((
            _HEADER.pack(_MAGIC, _MSG_MEASUREMENTS, 0, n),
            self._dev_bytes[4 * lo:4 * (lo + n)], self._mtype,
            self._tick[1][lo:lo + n].tobytes(),
            np.full(n, tick * TICK_S, np.float64).tobytes()))
        return struct.pack("<I", len(payload)) + payload


def fleets(cfg: dict, seed: int) -> list[Fleet]:
    """Every tenant's fleet of one configuration (benchmarks/configs)."""
    return [Fleet(seed, i, cfg["devices_per_tenant"], cfg["anomaly_rate"],
                  cfg["anomaly_magnitude"], cfg.get("frame_devices"))
            for i in range(cfg["tenants"])]


def tick_of(ts) -> np.ndarray:
    """Event times back to tick numbers (exact: ticks are whole minutes)."""
    return np.rint(np.asarray(ts, np.float64) / TICK_S).astype(np.int64)

"""Device places.

Parity: paddle/fluid/platform/place.h — CPUPlace/CUDAPlace. Here the
native accelerator is TPU (PJRT device via JAX); CUDAPlace is kept as an
alias so reference programs run by swapping nothing. A Place resolves to a
concrete jax.Device, and the Executor uses it for device_put and as the
jit compile target.
"""
import jax

__all__ = ["Place", "CPUPlace", "TPUPlace", "CUDAPlace", "CUDAPinnedPlace",
           "core_place_of"]


class Place:
    platform = None

    def __init__(self, device_id=0):
        self.device_id = int(device_id)

    def jax_device(self):
        """The local jax.Device this place names. An explicit place is a
        promise about where the program runs: no device of the place's
        platform, or a device_id past the last one, raises — it never
        resolves to some other device."""
        # LOCAL devices only: under multi-process
        # (jax.distributed.initialize) jax.devices() spans every host,
        # and placing a single-device computation on another host's
        # device is impossible (non-addressable)
        try:
            devs = jax.local_devices(backend=self.platform)
        except RuntimeError as e:      # jax: "Unknown backend tpu"
            raise RuntimeError(
                f"{self!r}: no {self.platform!r} backend in this "
                f"process (default backend: "
                f"{jax.default_backend()!r})") from e
        if not 0 <= self.device_id < len(devs):
            raise RuntimeError(
                f"{self!r}: this process has {len(devs)} local "
                f"{self.platform!r} device(s)")
        return devs[self.device_id]

    def __eq__(self, other):
        return (type(self) is type(other)
                and self.device_id == other.device_id)

    def __hash__(self):
        return hash((type(self).__name__, self.device_id))

    def __repr__(self):
        return f"{type(self).__name__}({self.device_id})"


class CPUPlace(Place):
    platform = "cpu"

    def __init__(self):
        super().__init__(0)


class TPUPlace(Place):
    """Accelerator place backed by a PJRT TPU device (the reference's
    CUDAPlace analog; see BASELINE.json north-star)."""
    platform = "tpu"


# Compatibility aliases: reference programs say fluid.CUDAPlace(i) (and
# fluid.CUDAPinnedPlace() for pinned host staging buffers); on this
# framework the accelerator is TPU, and "pinned host memory" has no
# separate notion under PJRT — host arrays are staged by device_put — so
# both names resolve to the nearest real place.
CUDAPlace = TPUPlace
CUDAPinnedPlace = CPUPlace


def core_place_of(place):
    """`place` itself, or — for None — the place of the process's
    default backend (callers that accept None report the choice)."""
    if isinstance(place, Place):
        return place
    if place is None:
        return TPUPlace(0) if jax.default_backend() == "tpu" \
            else CPUPlace()
    raise TypeError(f"not a Place: {place!r}")

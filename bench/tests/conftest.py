"""Four virtual CPU devices, so that the rescale cell's path runs here."""
import os

os.environ["XLA_FLAGS"] = " ".join(filter(None, [
    os.environ.get("XLA_FLAGS"), "--xla_force_host_platform_device_count=4"]))

import os

# The benchmark's tests run on the CPU; the data-parallel ones need several
# host devices, carved out before JAX initializes (the same count as the
# program's own test suite, so that either file may be loaded first).
os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8").strip()

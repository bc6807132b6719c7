import os

# The sharded round body's tests drive it on four virtual CPU devices;
# the flag has to be set before JAX starts its CPU client. A device count
# already given (by the repository's own tests/conftest.py, or by the
# caller) wins.
if "--xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=4").strip()

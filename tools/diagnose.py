"""Environment diagnostic for issue reports (parity: tools/diagnose.py —
OS/hardware/python/deps/framework checks; the reference also probed
website reachability, which is skipped by default here: TPU pods are
routinely egress-less, pass --network to attempt it).

    python tools/diagnose.py [--network]

The device is asked for in this process: it is the one that holds the
chip while it runs, so run it when nothing else is using the chip.
"""
import argparse
import os
import platform
import subprocess
import sys
import time

# runnable from anywhere, like the reference's tool (the repo layout
# puts the package one level up from tools/)
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)


def _section(title):
    print("----------" + title + "----------", flush=True)


def check_platform():
    _section("Platform Info")
    print("Platform     :", platform.platform())
    print("system       :", platform.system())
    print("node         :", platform.node())
    print("release      :", platform.release())
    print("version      :", platform.version())


def check_hardware():
    _section("Hardware Info")
    print("machine      :", platform.machine())
    print("processor    :", platform.processor() or "n/a")
    if platform.system() == "Linux":
        try:
            out = subprocess.run(["lscpu"], capture_output=True, text=True,
                                 timeout=10).stdout
            for line in out.splitlines():
                if any(k in line for k in ("Architecture", "Model name",
                                           "CPU(s)", "Thread", "MHz")):
                    print(line)
        except (OSError, subprocess.TimeoutExpired):
            pass


def check_python():
    _section("Python Info")
    print("Version      :", platform.python_version())
    print("Compiler     :", platform.python_compiler())
    print("Build        :", platform.python_build())


def check_deps():
    _section("Dependency Versions")
    for mod in ("numpy", "jax", "jaxlib", "flax", "optax"):
        try:
            m = __import__(mod)
            print("%-12s : %s" % (mod, getattr(m, "__version__", "?")))
        except ImportError:
            print("%-12s : NOT INSTALLED" % mod)


def check_framework():
    _section("MXNet-TPU Info")
    t0 = time.time()
    try:
        import mxnet_tpu as mx
        print("Version      :", mx.__version__)
        print("Directory    :", os.path.dirname(mx.__file__))
        print("Import time  : %.2fs" % (time.time() - t0))
    except Exception as e:  # noqa: BLE001 — diagnostic must keep going
        print("IMPORT FAILED:", e)
        return
    _section("Device Info")
    try:
        import jax
        devs = jax.devices()
        print("platform     :", devs[0].platform)
        print("device_kind  :", devs[0].device_kind)
        print("device count :", len(devs))
        print("tpu chips    :", mx.context.num_tpus())
    except Exception as e:  # noqa: BLE001 — diagnostic must keep going
        print("NO DEVICE    :", e)
    env = {k: v for k, v in os.environ.items() if k.startswith("MXNET_")}
    if env:
        _section("MXNET_* Environment")
        for k in sorted(env):
            print("%-28s = %s" % (k, env[k]))


def check_network(timeout=5):
    _section("Network Test")
    try:
        from urllib.request import urlopen
    except ImportError:
        print("urllib unavailable")
        return
    for name, url in (("PYPI", "https://pypi.python.org"),
                      ("Github", "https://github.com")):
        t0 = time.time()
        try:
            urlopen(url, timeout=timeout)
            print("%s ok in %.3fs" % (name, time.time() - t0))
        except Exception as e:  # noqa: BLE001
            print("%s FAILED (%s)" % (name, type(e).__name__))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--network", action="store_true",
                    help="also probe external sites (off by default: "
                         "TPU pods are typically egress-less)")
    args = ap.parse_args()
    check_platform()
    check_hardware()
    check_python()
    check_deps()
    check_framework()
    if args.network:
        check_network()


if __name__ == "__main__":
    main()

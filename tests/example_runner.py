"""The one way the test_examples_*.py files start a child Python process:
an example script, a tool, a harness or a notebook turned script, on the
CPU with 8 virtual devices and this checkout on PYTHONPATH."""
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = {**os.environ,
       "JAX_PLATFORMS": "cpu",
       "XLA_FLAGS": os.environ.get("XLA_FLAGS", "") +
       " --xla_force_host_platform_device_count=8",
       "PYTHONPATH": REPO}
# no child may outlive a fifth of tier-1's clock: one that hangs fails by
# name here instead of holding its worker until the suite is cut
TIMEOUT = 240


def run_python(argv, env=None, cwd=None, rc=0, timeout=TIMEOUT):
    """Run ``python *argv`` and hand back the finished process; ``env``
    is laid over ENV, ``rc`` is the exit code the caller expects."""
    proc = subprocess.run([sys.executable, *argv], env={**ENV, **(env or {})},
                          cwd=cwd, capture_output=True, text=True,
                          timeout=timeout)
    assert proc.returncode == rc, proc.stdout + proc.stderr
    return proc


def run_example(rel, *args, **kw):
    """Run the script at ``rel`` (relative to the checkout) from its own
    directory; returns stdout + stderr."""
    path = os.path.join(REPO, rel)
    kw.setdefault("cwd", os.path.dirname(path))
    proc = run_python([path, *args], **kw)
    return proc.stdout + proc.stderr


def notebook_script(rel, dest):
    """Write the code cells of the notebook at ``rel`` to ``dest`` as one
    script, in order."""
    import json
    with open(os.path.join(REPO, rel)) as f:
        nb = json.load(f)
    dest.write_text("\n\n".join("".join(c["source"]) for c in nb["cells"]
                                if c["cell_type"] == "code"))
    return str(dest)


def pack_rec(path, n, size):
    """Write ``n`` JPEG records of ``size`` x ``size`` noise, labelled by
    their index, to the .rec file at ``path``."""
    import numpy as np
    from mxnet_tpu import recordio
    img = (np.random.RandomState(0).rand(size, size, 3) * 255
           ).astype(np.uint8)
    w = recordio.MXRecordIO(path, "w")
    for i in range(n):
        header = recordio.IRHeader(0, float(i), i, 0)
        w.write(recordio.pack_img(header, np.roll(img, i, axis=0),
                                  quality=85, img_fmt=".jpg"))
    w.close()

"""Teardown fixture: the ps task spawns a grandchild and then blocks
forever — the tf.distribute.Server.join() shape whose processes were found
orphaned on the build box. It records its pids so the
test can assert the WHOLE process group is reaped when the session ends;
workers exit 0 immediately so the session SUCCEEDS while ps still runs."""
import json
import os
import subprocess
import sys
import time

pids_file = os.path.join(os.environ["TONY_LOG_DIR"], "ps-pids.json")
if os.environ["JOB_NAME"] == "ps":
    child = subprocess.Popen(
        [sys.executable, "-c", "import time; time.sleep(3600)"]
    )
    tmp = pids_file + ".tmp"
    with open(tmp, "w") as f:
        json.dump({"script": os.getpid(), "grandchild": child.pid}, f)
    os.rename(tmp, pids_file)
    time.sleep(3600)  # Server.join() analogue: never returns
else:
    # The worker gates session success on the ps having recorded its pids,
    # so the test never races the ps script's startup.
    deadline = time.time() + 60
    while not os.path.exists(pids_file):
        if time.time() > deadline:
            sys.exit(9)
        time.sleep(0.1)
sys.exit(0)

"""Workflow integration + version stamping — the analogue of
``TestTensorFlowJob`` (tony-azkaban/src/test) and the VersionInfo seam."""

import json
import sys
from pathlib import Path

import pytest

from tony_tpu.conf import keys
from tony_tpu.conf.configuration import TonyConfiguration
from tony_tpu.integrations import props_to_argv, submit_from_props
from tony_tpu.version import collect_version_info, inject_version_info

FIXTURES = Path(__file__).resolve().parent / "fixtures"


class TestPropsMapping:
    def test_direct_args_and_worker_env(self, tmp_path):
        argv = props_to_argv(
            {
                "executes": "train.py",
                "src_dir": "src",
                "task_params": "--epochs 3",
                "worker_env.FOO": "1",
                "worker_env.BAR": "x y",
            },
            job_id="job1",
            working_dir=tmp_path,
        )
        assert argv[:2] == ["--executes=train.py", "--src_dir=src"]
        assert "--shell_env=BAR=x y" in argv
        assert "--shell_env=FOO=1" in argv

    def test_option_like_task_params_survive_argparse(self, tmp_path):
        """task_params='--fast' must parse (the --name=value form; bare
        ['--task_params', '--fast'] would SystemExit in argparse)."""
        from tony_tpu.client.client import build_arg_parser

        argv = props_to_argv(
            {"executes": "t.py", "task_params": "--fast"},
            job_id="j", working_dir=tmp_path,
        )
        args, rest = build_arg_parser().parse_known_args(argv)
        assert args.task_params == "--fast" and rest == []

    def test_tony_props_become_conf_file(self, tmp_path):
        argv = props_to_argv(
            {
                "executes": "t.py",
                "tony.worker.instances": "3",
                "tony.application.framework": "pytorch",
            },
            job_id="jobX",
            working_dir=tmp_path,
        )
        conf_arg = next(a for a in argv if a.startswith("--conf_file="))
        conf_file = Path(conf_arg.split("=", 1)[1])
        assert conf_file.parent.name == "_tony-conf-jobX"
        body = json.loads(conf_file.read_text())
        assert body["tony.worker.instances"] == "3"
        assert body["tony.application.framework"] == "pytorch"

    def test_unknown_submitter_rejected(self, tmp_path):
        with pytest.raises(ValueError, match="unknown submitter"):
            submit_from_props({}, "j", submitter="bogus",
                              working_dir=tmp_path)

    def test_round_trip_local_submission(self, tmp_path):
        """The done-criterion: a props dict maps to
        a successful local submission end-to-end."""
        rc = submit_from_props(
            {
                "executes": str(FIXTURES / "check_env.py"),
                "python_binary_path": sys.executable,
                "worker_env.USER_SHELL_VAR": "propagated",
                "tony.worker.instances": "1",
                "tony.ps.instances": "0",
                "tony.am.stop-grace": "0",
            },
            job_id="wf1",
            submitter="local",
            working_dir=tmp_path,
        )
        assert rc == 0


class TestVersionInfo:
    def test_collect_in_git_checkout(self):
        info = collect_version_info()
        assert len(info["revision"]) == 40  # this repo IS a git checkout
        assert info["branch"] and info["user"]
        assert info["version"] == "0.1.0"

    def test_injected_into_conf_and_frozen(self, tmp_path):
        conf = TonyConfiguration()
        inject_version_info(conf)
        assert len(conf.get_str(keys.K_VERSION_INFO_REVISION)) == 40
        # rides the frozen conf (what executors + history see)
        final = tmp_path / "tony-final.json"
        conf.write_final(final)
        frozen = json.loads(final.read_text())
        assert frozen[keys.K_VERSION_INFO_REVISION] == conf.get_str(
            keys.K_VERSION_INFO_REVISION
        )

    def test_client_stamps_on_init(self, tmp_path):
        from tony_tpu.client.client import TonyClient

        client = TonyClient().init(["--executes", "x.py"])
        assert len(client.conf.get_str(keys.K_VERSION_INFO_REVISION)) == 40

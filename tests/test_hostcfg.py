"""configure check/init host stages (fdctl configure parity)."""

from firedancer_tpu.utils import hostcfg


def test_all_checks_return_results():
    res = hostcfg.run("check")
    stages = {r.stage for r in res}
    assert {"shm", "nofile", "cpus", "thp", "clocksource",
            "swap"} <= stages
    for r in res:
        assert r.status in (hostcfg.OK, hostcfg.WARN, hostcfg.FAIL)
        assert r.detail
        if r.status != hostcfg.OK:
            assert r.remedy  # every failure names its fix


def test_init_raises_nofile_soft_limit():
    import resource

    soft, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
    try:
        if hard >= 4096:
            resource.setrlimit(resource.RLIMIT_NOFILE, (1024, hard))
            res = {r.stage: r for r in hostcfg.run("init")}
            assert res["nofile"].status == hostcfg.OK
            got, _ = resource.getrlimit(resource.RLIMIT_NOFILE)
            assert got >= 4096
    finally:
        resource.setrlimit(resource.RLIMIT_NOFILE, (soft, hard))


def test_configure_cli(capsys):
    from firedancer_tpu.__main__ import main

    rc = main(["configure", "check"])
    out = capsys.readouterr().out
    assert "shm" in out and rc in (0, 1)

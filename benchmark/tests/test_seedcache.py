"""The seed cache: built on a miss, found on a hit, never stale."""

import os

from benchmark.harness import seedcache


def test_miss_then_hit_and_a_killed_build_leaves_nothing(tmp_path, monkeypatch):
    monkeypatch.setattr(seedcache, "ROOT", str(tmp_path))
    made = []

    def build(d):
        made.append(d)
        with open(os.path.join(d, "data"), "w") as f:
            f.write("x")

    path, hit = seedcache.ensure("7-abc", "vocab", build)
    assert not hit and os.path.isfile(os.path.join(path, "data"))
    assert seedcache.ensure("7-abc", "vocab", build) == (path, True)
    assert len(made) == 1

    def killed(d):
        raise KeyboardInterrupt

    try:
        seedcache.ensure("8-abc", "vocab", killed)
    except KeyboardInterrupt:
        pass
    path, hit = seedcache.ensure("8-abc", "vocab", build)
    assert not hit and len(made) == 2


def test_the_key_follows_seed_parameters_and_every_source_file(tmp_path):
    a, b = tmp_path / "generator.py", tmp_path / "program_writer.py"
    a.write_text("one")
    b.write_text("two")
    files = [str(a), str(b)]
    key = seedcache.key(3, files, {"n": 1})
    assert key.startswith("3-") and key == seedcache.key(3, files[::-1], {"n": 1})
    assert key != seedcache.key(4, files, {"n": 1})
    assert key != seedcache.key(3, files, {"n": 2})
    b.write_text("two, edited")          # the program's writer changed
    assert key != seedcache.key(3, files, {"n": 1})


def test_only_the_newest_directories_are_kept(tmp_path, monkeypatch):
    monkeypatch.setattr(seedcache, "ROOT", str(tmp_path))
    monkeypatch.setattr(seedcache, "KEEP", 3)
    for seed in range(5):
        path, _ = seedcache.ensure(f"{seed}-abc", "x", lambda d: None)
        os.utime(os.path.dirname(path), (seed, seed))
    assert sorted(os.listdir(tmp_path)) == ["2-abc", "3-abc", "4-abc"]

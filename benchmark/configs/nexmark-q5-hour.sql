-- NEXmark Query 5, hot items, at the specification's sixty slides to a
-- window: the auctions with the most bids in a 60 s window sliding by 1 s
-- (Bid [RANGE 60 MINUTE SLIDE 1 MINUTE] cut by sixty), read from the nexmark
-- connector. The dollar names are filled from the configuration's generator
-- settings, the traffic mix's rate and --seed.
CREATE TABLE nexmark (
  "bid" BOOLEAN, "bid.auction" BIGINT
) WITH (
  connector = 'nexmark',
  inter_event_micros = $inter_event_micros,
  first_event_micros = $first_event_micros,
  event_rate = $event_rate,
  seed = $seed
);
CREATE TABLE top_auctions (
  auction BIGINT, num BIGINT, ws TIMESTAMP
) WITH (connector = '$sink', type = 'sink');
INSERT INTO top_auctions
SELECT AuctionBids.auction, AuctionBids.num, AuctionBids.window.start
FROM (
  SELECT "bid.auction" AS auction, count(*) AS num,
    hop(interval '1 second', interval '60 seconds') AS window
  FROM nexmark WHERE "bid" GROUP BY "bid.auction", window
) AS AuctionBids
JOIN (
  SELECT max(CountBids.num) AS maxn, CountBids.window
  FROM (
    SELECT count(*) AS num,
      hop(interval '1 second', interval '60 seconds') AS window
    FROM nexmark WHERE "bid" GROUP BY "bid.auction", window
  ) AS CountBids
  GROUP BY CountBids.window
) AS MaxBids
ON AuctionBids.window = MaxBids.window AND AuctionBids.num >= MaxBids.maxn;
